"""Compile the main path's device programs for a described TPU v5e.

No chip is attached: ``jax.experimental.topologies`` describes a v5e
and the TPU compiler builds for it, so shape, tiling and fast-memory
errors that interpret mode cannot see surface here.  The kernels are
compiled at the widths the paper-scale fit (n = 1e6, 3-d, MinPts 100)
dispatches: ``grid_block`` 64 grids per call, own rows padded to 128,
candidate tiers up to 16384, features padded to the 128-lane width.

The topology is described inside a module fixture (never at import),
and this is the only test file that describes it: the process that
describes it holds the TPU library until it exits.
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.device_dbscan import GritCaps, device_dbscan
from repro.kernels import ops, pairwise

G, M, N, D = 64, 128, 16384, pairwise.LANE


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / topology in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device executable cannot be read back from the
    # persistent cache, so keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


KERNELS = {
    "eps_count_batch_pallas": (pairwise.eps_count_batch_pallas, (1, 1)),
    "eps_count_band_batch_pallas": (pairwise.eps_count_band_batch_pallas,
                                    (2,)),
    "row_min_batch_pallas": (pairwise.row_min_batch_pallas, None),
    "row_min2_batch_pallas": (pairwise.row_min2_batch_pallas, None),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_fit_kernel_compiles_at_paper_scale_widths(one_chip, name):
    fn, thresh = KERNELS[name]
    args = [_spec((G, M, D), jnp.float32, one_chip),
            _spec((G, N, D), jnp.float32, one_chip)]
    if thresh is not None:
        args.append(_spec(thresh, jnp.float32, one_chip))
    compiled = jax.jit(functools.partial(fn, interpret=False)).lower(
        *args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the custom call is named after the op (the pallas_call's name=),
    # whatever the Python kernel function is called
    assert re.search(rf"%{name[:-len('_pallas')]}(\.\d+)? = .*custom-call\(",
                     text)


def test_flat_resident_distance_op_compiles_at_large_bucket(one_chip):
    """The delta engine's flat stage at T = 2^20 elements over a
    resident buffer of 2^21 rows (a 1e6-point index after inserts)."""
    T, rows = 1 << 20, 1 << 21
    idx = _spec((T,), jnp.int32, one_chip)
    compiled = ops._pairwise_d2_flat_res_jit.lower(
        _spec((rows, 3), jnp.float32, one_chip), idx, idx,
        _spec((T, 3), jnp.float32, one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == T * 4


@pytest.fixture
def pallas_branch(monkeypatch):
    """Steer the batched wrappers onto their TPU branch (the CPU
    backend would pick the tiled loop); traces cached under either
    branch are dropped on both sides of the test."""
    monkeypatch.setattr(ops, "_use_batch_pallas", lambda interpret: True)
    jax.clear_caches()
    yield
    monkeypatch.undo()
    jax.clear_caches()


def test_device_fit_program_compiles_with_pallas_plane(one_chip,
                                                       pallas_branch):
    caps = GritCaps(grid_cap=128, frontier_cap=32, k_cap=24, c_cap=192,
                    m_cap=40, pair_cap=512, grid_block=64, pair_block=128,
                    merge_iters=84, use_kernels=True)
    n = 2048
    compiled = device_dbscan.lower(
        _spec((n, 3), jnp.float32, one_chip), 200.0, 9, caps,
        point_valid=_spec((n,), jnp.bool_, one_chip)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the fit's two kernels by their stable names: the benchmark finds
    # their device time by ``eps_count_batch|row_min_batch``
    kernels = set(re.findall(r"%([A-Za-z0-9_]+?)(?:\.\d+)? = [^\n]*"
                             r"custom-call\(", text))
    assert kernels == {"eps_count_batch", "row_min_batch"}
