"""The sharded fit on four devices against the benchmark's own plain
reference, in the default run (no ``slow`` mark): the four-chip cell's
configuration at a small table, through ``cluster(engine="distributed")``
on a four-device mesh, must be exact DBSCAN, shard every row, count the
halo rows its exchange selects, and observe each host stage once per
fit.

Four devices need ``--xla_force_host_platform_device_count`` in a fresh
process, as in ``tests/test_distributed.py`` (the pytest process keeps
seeing one device)."""

import os
import subprocess
import sys
import textwrap

ROOT = os.path.join(os.path.dirname(__file__), "..")


def test_sharded_fit_matches_the_benchmark_reference():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.pathsep.join([os.path.join(ROOT, "src"), ROOT])
    out = subprocess.run([sys.executable, "-c", textwrap.dedent("""
        import json
        import numpy as np, jax
        from jax.sharding import Mesh
        from bench import gen, main, reference
        from repro import obs
        from repro.dist import shard_points_by_slab
        from repro.dist.halo import halo_census
        from repro.engine import cluster

        cfg = json.loads((main.BENCH / "configs" /
                          "ss3d-4m-batch.json").read_text())
        cfg["data"]["n"] = 40_000
        pts = gen.make_table(cfg, 3000001502)
        eps, min_pts = cfg["eps"], cfg["min_pts"]
        assert jax.device_count() == cfg["shards"] == 4
        mesh = Mesh(np.array(jax.devices()), ("shard",))
        ref = reference.fit(pts, eps, min_pts)

        pts_sh, valid_sh, _ = shard_points_by_slab(pts, eps, 4)
        assert valid_sh.any(axis=1).all(), valid_sh.sum(axis=1)
        selected = halo_census(pts_sh, valid_sh, eps, 1)[0]
        reg = obs.registry()
        stages = ("dist.fit.pack_s", "dist.fit.transfer_s",
                  "dist.fit.unpack_s")
        for fits in (1, 2):
            res = cluster(pts, eps, min_pts, engine="distributed",
                          mesh=mesh)
            assert len(res.attempts) == 1, res.attempts
            got = reference.compare_fit(ref, res.labels, res.core)
            assert got == {"core_flags": 0, "labels": 0}, got
            rows = reg.counter("dist.halo.rows").value
            assert rows > 0 and rows == fits * selected, (rows, selected)
            for h in stages:
                assert len(reg.histogram(h).values()) == fits, h
        print("OK", int(ref.core.sum()), int(ref.comp.max()) + 1, selected)
    """)], env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    assert out.stdout.startswith("OK"), out.stdout
