"""BENCHMARK.json against the files it names, loading by name, and the
generators' seed discipline."""

import json
import re

import numpy as np
import pytest

from bench import gen, main

SPEC = json.loads((main.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_spec_keys_and_names():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(x["name"] for x in SPEC["end_to_end"] + SPEC["per_layer"])) \
        == len(SPEC["end_to_end"]) + len(SPEC["per_layer"])
    assert "setup_s" in {m["name"] for m in SPEC["end_to_end"]}
    assert 1 <= SPEC["run_seconds"] <= 51


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_loads_by_name(cell):
    c, config, traffic, e2e, layer = main.load_cell(cell)
    assert config["name"] == c["config"]
    assert {m["name"] for m in e2e} >= {"setup_s"} and len(e2e) >= 2
    assert layer, "every cell reports a per-layer metric"
    assert (main.BENCH / "drivers" / f"{traffic['driver']}.py").is_file()
    for m in layer:
        assert m["moves"] in {x["name"] for x in e2e}
        assert callable(main.load_module("metrics", m["name"]).read)
    entry = next(x for x in SPEC["configs"]
                 if x["name"] == c["config"])
    for key in entry["reduced"]:
        assert key in config["data"] or key in config


def test_metric_readers_find_nothing_in_an_empty_record():
    for m in SPEC["per_layer"]:
        assert main.load_module("metrics", m["name"]).read({}) is None


CFG = {"data": dict(n=3000, d=3, variant="simden", restarts=10,
                    c_reset=100, r_vicinity=100.0, r_shift=150.0,
                    noise_frac=0.0001, domain=1e5, seed=0, round=True)}


def test_table_same_rows_every_seed_in_another_order():
    a, b, c = (gen.make_table(CFG, s) for s in (1, 1, 2 ** 31 + 5))
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)
    assert np.array_equal(np.unique(a, axis=0), np.unique(c, axis=0))
    assert np.array_equal(a, np.rint(a))
