"""The four-chip fit cell end to end, off the chip: ``bench/main.py
--rehearse`` on four virtual CPU devices runs set-up, the window and
the check at a small table and exits 3, every fit exact against the
reference.  The harness itself fails a run whose window compiles, so
the exit code also says set-up warmed every shape the window used."""

import json
import os
import subprocess
import sys

from bench import main


def test_rehearsal_on_four_devices_is_exact(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run(
        [sys.executable, str(main.BENCH / "main.py"),
         "--workload", "ss3d-4m-batch.fit4", "--seed", "3000001599",
         "--seconds", "1", "--trace", "0", "--rehearse", "20000"],
        cwd=main.ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert out.returncode == 3, out.stderr[-3000:]
    line = next(x for x in out.stderr.splitlines()
                if x.startswith("[rehearsal] "))
    res = json.loads(line[len("[rehearsal] "):])
    assert res["correct"]
    assert {k: v["value"] for k, v in res["checks"].items()} == {
        "core_flags_differ": 0, "labels_not_dbscan": 0}
    assert res["device"]["count"] == 4
    assert res["attempted"] >= 1 and res["failed"] == 0
