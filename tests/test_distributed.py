"""Distributed DBSCAN + multi-device parity.

These need >1 device, so they run in a subprocess with
``--xla_force_host_platform_device_count`` (the main pytest process must
keep seeing exactly 1 device for all other tests).

All heavyweight (subprocess + multi-device compile): marked ``slow``,
covered by the nightly CI job.  The default run keeps a single-shard
distributed conformance case in tests/test_conformance.py.
"""

import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(snippet: str, devices: int = 4) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(snippet)],
                         env=env, capture_output=True, text=True,
                         timeout=540)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


def test_distributed_dbscan_exact_vs_brute():
    out = _run("""
        import numpy as np, jax
        from repro.data.seed_spreader import seed_spreader
        from repro.core.dbscan import brute_dbscan
        from repro.dist import distributed_dbscan, ClusterCaps
        from repro.core.device_dbscan import GritCaps
        from repro.core.validate import assert_dbscan_equivalent

        mesh = jax.make_mesh((4,), ("data",))
        caps = ClusterCaps(grit=GritCaps(grid_cap=512, frontier_cap=256,
                                         k_cap=64, c_cap=2048, m_cap=1024,
                                         pair_cap=4096, grid_block=64,
                                         pair_block=256),
                           halo_cap=512)
        for d, seed in [(2, 0), (3, 1), (5, 2)]:
            pts = seed_spreader(800, d, variant="simden", restarts=5,
                                seed=seed)
            eps, min_pts = 4000.0, 8
            labels, ovf = distributed_dbscan(pts, eps, min_pts, mesh, caps)
            assert not ovf
            ref = brute_dbscan(pts, eps, min_pts)
            assert_dbscan_equivalent(pts, eps, min_pts, ref, labels)
            print(f"d={d} OK")
    """)
    assert out.count("OK") == 3


def test_cluster_spanning_all_shards():
    """One long snake cluster crossing every slab boundary."""
    out = _run("""
        import numpy as np, jax
        from repro.core.dbscan import brute_dbscan
        from repro.dist import distributed_dbscan, ClusterCaps
        from repro.core.device_dbscan import GritCaps
        from repro.core.validate import assert_dbscan_equivalent

        rng = np.random.default_rng(0)
        t = np.linspace(0, 1, 600)
        snake = np.stack([t * 1e5, 5e4 + 1e4 * np.sin(6 * t)], 1)
        snake += rng.normal(scale=300.0, size=snake.shape)
        noise = rng.uniform(0, 1e5, size=(60, 2))
        pts = np.concatenate([snake, noise])
        mesh = jax.make_mesh((4,), ("data",))
        caps = ClusterCaps(grit=GritCaps(grid_cap=512, frontier_cap=256,
                                         k_cap=64, c_cap=2048, m_cap=1024,
                                         pair_cap=4096, grid_block=64,
                                         pair_block=256),
                           halo_cap=512)
        eps, min_pts = 2500.0, 5
        labels, ovf = distributed_dbscan(pts, eps, min_pts, mesh, caps)
        assert not ovf
        ref = brute_dbscan(pts, eps, min_pts)
        assert_dbscan_equivalent(pts, eps, min_pts, ref, labels)
        # the snake is one cluster even though it crosses all 4 slabs
        snake_labels = set(labels[:600]) - {-1}
        assert len(snake_labels) == 1, snake_labels
        print("SNAKE OK")
    """)
    assert "SNAKE OK" in out


def test_data_parallel_train_parity_with_single_device():
    """2-device data-parallel step == single-device step (same batch)."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.models import init_params
        from repro.train import (TrainCfg, make_train_step, init_state,
                                 get_optimizer)
        from repro.data.tokens import TokenPipeline

        cfg = get_config("qwen1.5-0.5b", smoke=True).with_overrides(
            dtype="float32", remat=False)
        params = init_params(cfg, jax.random.PRNGKey(0))
        opt = get_optimizer("adamw", weight_decay=0.0)
        tcfg = TrainCfg()
        step = make_train_step(cfg, tcfg, opt, lambda s: 1e-3)
        pipe = TokenPipeline(cfg.vocab_size, 16, 8, seed=0)
        batch = {"tokens": jnp.asarray(pipe.next_batch()["tokens"])}

        state = init_state(cfg, tcfg, opt, params)
        ref_state, ref_m = jax.jit(step)(state, batch)

        mesh = jax.make_mesh((2,), ("data",))
        sb = jax.device_put(batch["tokens"],
                            NamedSharding(mesh, P("data", None)))
        state2 = init_state(cfg, tcfg, opt, params)
        dp_state, dp_m = jax.jit(step)(state2, {"tokens": sb})
        assert abs(float(ref_m["loss"]) - float(dp_m["loss"])) < 1e-4
        for a, b in zip(jax.tree_util.tree_leaves(ref_state["params"]),
                        jax.tree_util.tree_leaves(dp_state["params"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=1e-5)
        print("PARITY OK")
    """, devices=2)
    assert "PARITY OK" in out


def test_cluster_step_lowers_on_production_mesh():
    """The shard_map cluster step must lower+compile on 16x16 (the same
    artifact the multi-pod dry-run exercises)."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.launch.mesh import make_production_mesh
        from repro.dist import make_cluster_step, ClusterCaps
        from repro.core.device_dbscan import GritCaps
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = make_production_mesh()          # 16 x 16 = 256 shards
        caps = ClusterCaps(grit=GritCaps(grid_cap=256, frontier_cap=128,
                                         k_cap=32, c_cap=512, m_cap=256,
                                         pair_cap=1024, grid_block=64,
                                         pair_block=256),
                           halo_cap=128)
        n_shard, d = 4096, 3
        step = make_cluster_step(mesh, 3000.0, 10, caps, n_shard, d)
        N = 256 * n_shard
        pts = jax.ShapeDtypeStruct(
            (N, d), jnp.float32,
            sharding=NamedSharding(mesh, P(("data", "model"), None)))
        valid = jax.ShapeDtypeStruct(
            (N,), jnp.bool_,
            sharding=NamedSharding(mesh, P(("data", "model"))))
        compiled = jax.jit(step).lower(pts, valid).compile()
        assert compiled is not None
        print("LOWERED OK")
    """, devices=512)
    assert "LOWERED OK" in out


def test_shardmap_moe_matches_reference():
    """Manual-SPMD MoE paths (model-local and expert-parallel all-to-all)
    vs the dense oracle, on a 2x2 fake mesh."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.models.config import LMConfig, MoECfg
        from repro.models import moe as M

        def check(E, mesh_shape, fn_name):
            cfg = LMConfig(name="t", family="moe", num_layers=1, d_model=32,
                           num_heads=2, num_kv_heads=2, head_dim=16, d_ff=64,
                           vocab_size=64, dtype="float32",
                           moe=MoECfg(num_experts=E, top_k=2, d_ff=64,
                                      capacity_factor=16.0))
            mesh = jax.make_mesh(mesh_shape, ("data", "model"))
            p = M.moe_params(cfg, jax.random.PRNGKey(0))
            x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32),
                                  jnp.float32)
            y_ref, _ = M.moe_forward_dense_fallback(cfg, p, x)
            fn = getattr(M, fn_name)
            y, aux = jax.jit(lambda p, x: fn(cfg, p, x, mesh, ("data",),
                                             "model"))(p, x)
            err = float(jnp.abs(y - y_ref).max())
            assert err < 1e-4, (fn_name, E, err)
            print(fn_name, E, "OK")

        check(4, (2, 2), "moe_forward_shardmap")    # experts over model
        check(2, (1, 4), "moe_forward_shardmap")    # ff-split virtual experts
        check(4, (2, 2), "moe_forward_shardmap_ep") # expert-parallel a2a
        check(8, (2, 2), "moe_forward_shardmap_ep")
    """)
    assert out.count("OK") == 4

