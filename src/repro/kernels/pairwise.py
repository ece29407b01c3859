"""Blocked pairwise-distance Pallas kernels (TPU target, MXU-tiled).

The DBSCAN hot spots (core identification, FastMerging nearest queries,
border assignment) all reduce to tiles of squared Euclidean distances
between two point sets.  On TPU the `-2 a.b` term is an MXU matmul, so the
tile shapes are chosen MXU-aligned: 128 x 128 output tiles, feature dim
padded to the 128 lane width by the ops.py wrappers.

Kernels (one `pl.pallas_call` each, explicit VMEM BlockSpecs):

* ``eps_count_kernel``  -- per-row count of other-set points within eps.
* ``row_min_kernel``    -- per-row (min squared distance, argmin index).
* ``eps_count_batch_*`` / ``row_min_batch_*`` -- the same contractions
  with a leading grid-batch dimension, one (a-set, b-set) pair per grid
  of the DBSCAN pipeline; the batch axis is the outermost grid dimension
  so each (g, i) output block still accumulates across the j axis.

All iterate a (..., i, j) grid over (rows, cols) tiles and accumulate
across the j axis in the output block (revisited per i), the standard
Pallas accumulation pattern.  Padding policy (see ops.py): padded B-rows
carry coordinates so far away they can never satisfy a predicate (and
per-row validity masks are folded into the same FAR coordinates before
the call); padded A-rows produce garbage that callers slice off.

Each ``pl.pallas_call`` carries an explicit ``name=`` equal to the op it
implements (``eps_count``, ``row_min``, ``eps_count_batch``,
``eps_count_band_batch``, ``row_min2_batch``, ``row_min_batch``): the
custom call's kernel name, by which a profiler trace finds the kernel,
so renaming a Python kernel function cannot hide it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_M = 128
BLOCK_N = 128
LANE = 128


def _sq_dist_tile(a, b):
    """[BM, D] x [BN, D] -> [BM, BN] squared distances (f32, MXU dot).

    The contraction runs at ``Precision.HIGHEST`` (full f32 passes on
    the MXU): at the default precision the TPU rounds the operands to
    bf16, whose 8-bit mantissa cannot hold stencil-scale coordinates,
    so eps predicates would flip far outside any f32 error band."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    ab = jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
    aa = jnp.sum(a * a, axis=1, keepdims=True)        # [BM, 1]
    bb = jnp.sum(b * b, axis=1, keepdims=True).T      # [1, BN]
    return jnp.maximum(aa + bb - 2.0 * ab, 0.0)


# --------------------------------------------------------------------------
# eps-count
# --------------------------------------------------------------------------

def _eps_count_kernel(a_ref, b_ref, eps2_ref, out_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    d2 = _sq_dist_tile(a_ref[...], b_ref[...])
    hit = (d2 <= eps2_ref[0, 0]).astype(jnp.int32)
    out_ref[...] += jnp.sum(hit, axis=1, keepdims=True)


def eps_count_pallas(a: jnp.ndarray, b: jnp.ndarray, eps2: jnp.ndarray,
                     *, block_m: int = BLOCK_M, block_n: int = BLOCK_N,
                     interpret: bool = False) -> jnp.ndarray:
    """a: [M, D], b: [N, D] (M % block_m == N % block_n == 0, D == LANE).

    Returns [M, 1] int32 counts of b-rows within sqrt(eps2) of each a-row.
    """
    M, D = a.shape
    N = b.shape[0]
    grid = (M // block_m, N // block_n)
    return pl.pallas_call(
        _eps_count_kernel,
        name="eps_count",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, D), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, D), lambda i, j: (j, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, 1), jnp.int32),
        interpret=interpret,
    )(a, b, eps2.reshape(1, 1).astype(jnp.float32))


# --------------------------------------------------------------------------
# row-min (+ argmin)
# --------------------------------------------------------------------------

def _row_min_kernel(a_ref, b_ref, min_ref, arg_ref, *, block_n: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        min_ref[...] = jnp.full_like(min_ref, jnp.inf)
        arg_ref[...] = jnp.full_like(arg_ref, -1)

    d2 = _sq_dist_tile(a_ref[...], b_ref[...])
    tile_min = jnp.min(d2, axis=1, keepdims=True)             # [BM, 1]
    tile_arg = jnp.argmin(d2, axis=1).astype(jnp.int32)[:, None]
    better = tile_min < min_ref[...]
    min_ref[...] = jnp.where(better, tile_min, min_ref[...])
    arg_ref[...] = jnp.where(better, tile_arg + j * block_n, arg_ref[...])


def row_min_pallas(a: jnp.ndarray, b: jnp.ndarray,
                   *, block_m: int = BLOCK_M, block_n: int = BLOCK_N,
                   interpret: bool = False):
    """a: [M, D], b: [N, D] (aligned as in ``eps_count_pallas``).

    Returns ([M, 1] f32 min squared distance, [M, 1] int32 argmin row).
    """
    M, D = a.shape
    N = b.shape[0]
    grid = (M // block_m, N // block_n)
    return pl.pallas_call(
        functools.partial(_row_min_kernel, block_n=block_n),
        name="row_min",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, D), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, D), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_m, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_m, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, 1), jnp.float32),
            jax.ShapeDtypeStruct((M, 1), jnp.int32),
        ],
        interpret=interpret,
    )(a, b)


# --------------------------------------------------------------------------
# batched forms: leading grid-batch dimension (one DBSCAN grid per slot)
# --------------------------------------------------------------------------

def _eps_count_batch_kernel(a_ref, b_ref, eps2_ref, out_ref):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    d2 = _sq_dist_tile(a_ref[0, :, :], b_ref[0, :, :])
    hit = (d2 <= eps2_ref[0, 0]).astype(jnp.int32)
    out_ref[0, :, :] += jnp.sum(hit, axis=1, keepdims=True)


def eps_count_batch_pallas(a: jnp.ndarray, b: jnp.ndarray, eps2: jnp.ndarray,
                           *, block_m: int = BLOCK_M, block_n: int = BLOCK_N,
                           interpret: bool = False) -> jnp.ndarray:
    """a: [G, M, D], b: [G, N, D] (M % block_m == N % block_n == 0,
    D == LANE).  Returns [G, M, 1] int32 counts of b-rows of batch g
    within sqrt(eps2) of each a-row of batch g."""
    G, M, D = a.shape
    N = b.shape[1]
    grid = (G, M // block_m, N // block_n)
    return pl.pallas_call(
        _eps_count_batch_kernel,
        name="eps_count_batch",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_m, D), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, block_n, D), lambda g, i, j: (g, j, 0)),
            pl.BlockSpec((1, 1), lambda g, i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_m, 1), lambda g, i, j: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((G, M, 1), jnp.int32),
        interpret=interpret,
    )(a, b, eps2.reshape(1, 1).astype(jnp.float32))


def _eps_count_band_batch_kernel(a_ref, b_ref, eps2_ref, lo_ref, hi_ref):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        lo_ref[...] = jnp.zeros_like(lo_ref)
        hi_ref[...] = jnp.zeros_like(hi_ref)

    d2 = _sq_dist_tile(a_ref[0, :, :], b_ref[0, :, :])
    hit_lo = (d2 <= eps2_ref[0, 0]).astype(jnp.int32)
    hit_hi = (d2 <= eps2_ref[0, 1]).astype(jnp.int32)
    lo_ref[0, :, :] += jnp.sum(hit_lo, axis=1, keepdims=True)
    hi_ref[0, :, :] += jnp.sum(hit_hi, axis=1, keepdims=True)


def eps_count_band_batch_pallas(a: jnp.ndarray, b: jnp.ndarray,
                                eps2_band: jnp.ndarray,
                                *, block_m: int = BLOCK_M,
                                block_n: int = BLOCK_N,
                                interpret: bool = False):
    """Two-threshold twin of ``eps_count_batch_pallas``.

    a: [G, M, D], b: [G, N, D] (aligned), eps2_band: [2] (lo2, hi2)
    squared thresholds.  Returns two [G, M, 1] int32 count arrays --
    hits at ``d2 <= lo2`` and at ``d2 <= hi2``, accumulated in one
    sweep over the same distance tiles (the guard-band decision needs
    both counts and the tiles dominate the cost)."""
    G, M, D = a.shape
    N = b.shape[1]
    grid = (G, M // block_m, N // block_n)
    return pl.pallas_call(
        _eps_count_band_batch_kernel,
        name="eps_count_band_batch",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_m, D), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, block_n, D), lambda g, i, j: (g, j, 0)),
            pl.BlockSpec((1, 2), lambda g, i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_m, 1), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, block_m, 1), lambda g, i, j: (g, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((G, M, 1), jnp.int32),
            jax.ShapeDtypeStruct((G, M, 1), jnp.int32),
        ],
        interpret=interpret,
    )(a, b, eps2_band.reshape(1, 2).astype(jnp.float32))


def _row_min2_batch_kernel(a_ref, b_ref, min_ref, min2_ref, arg_ref,
                           *, block_n: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        min_ref[...] = jnp.full_like(min_ref, jnp.inf)
        min2_ref[...] = jnp.full_like(min2_ref, jnp.inf)
        arg_ref[...] = jnp.full_like(arg_ref, -1)

    d2 = _sq_dist_tile(a_ref[0, :, :], b_ref[0, :, :])
    tile_arg = jnp.argmin(d2, axis=1).astype(jnp.int32)[:, None]
    tile_min = jnp.min(d2, axis=1, keepdims=True)             # [BM, 1]
    cols = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
    d2_wo = jnp.where(cols == tile_arg, jnp.inf, d2)
    tile_min2 = jnp.min(d2_wo, axis=1, keepdims=True)
    prev_min = min_ref[0, :, :]
    better = tile_min < prev_min
    # merge the two sorted (first, second) pairs: the global runner-up
    # is the smaller of both runners-up and the loser of the two firsts
    loser = jnp.maximum(prev_min, tile_min)
    min2_ref[0, :, :] = jnp.minimum(jnp.minimum(min2_ref[0, :, :],
                                                tile_min2), loser)
    min_ref[0, :, :] = jnp.where(better, tile_min, prev_min)
    arg_ref[0, :, :] = jnp.where(better, tile_arg + j * block_n,
                                 arg_ref[0, :, :])


def row_min2_batch_pallas(a: jnp.ndarray, b: jnp.ndarray,
                          *, block_m: int = BLOCK_M,
                          block_n: int = BLOCK_N,
                          interpret: bool = False):
    """``row_min_batch_pallas`` plus the runner-up distance.

    a: [G, M, D], b: [G, N, D] (aligned).  Returns ([G, M, 1] f32 min,
    [G, M, 1] f32 second-smallest slot distance, [G, M, 1] int32
    argmin).  The runner-up feeds the device path's argmin-certainty
    test: a gap wider than the float32 error band proves the float64
    argmin is the same row."""
    G, M, D = a.shape
    N = b.shape[1]
    grid = (G, M // block_m, N // block_n)
    return pl.pallas_call(
        functools.partial(_row_min2_batch_kernel, block_n=block_n),
        name="row_min2_batch",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_m, D), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, block_n, D), lambda g, i, j: (g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_m, 1), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, block_m, 1), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, block_m, 1), lambda g, i, j: (g, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((G, M, 1), jnp.float32),
            jax.ShapeDtypeStruct((G, M, 1), jnp.float32),
            jax.ShapeDtypeStruct((G, M, 1), jnp.int32),
        ],
        interpret=interpret,
    )(a, b)


def _row_min_batch_kernel(a_ref, b_ref, min_ref, arg_ref, *, block_n: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        min_ref[...] = jnp.full_like(min_ref, jnp.inf)
        arg_ref[...] = jnp.full_like(arg_ref, -1)

    d2 = _sq_dist_tile(a_ref[0, :, :], b_ref[0, :, :])
    tile_min = jnp.min(d2, axis=1, keepdims=True)             # [BM, 1]
    tile_arg = jnp.argmin(d2, axis=1).astype(jnp.int32)[:, None]
    better = tile_min < min_ref[0, :, :]
    min_ref[0, :, :] = jnp.where(better, tile_min, min_ref[0, :, :])
    arg_ref[0, :, :] = jnp.where(better, tile_arg + j * block_n,
                                 arg_ref[0, :, :])


def row_min_batch_pallas(a: jnp.ndarray, b: jnp.ndarray,
                         *, block_m: int = BLOCK_M, block_n: int = BLOCK_N,
                         interpret: bool = False):
    """a: [G, M, D], b: [G, N, D] (aligned as in
    ``eps_count_batch_pallas``).  Returns ([G, M, 1] f32 min squared
    distance, [G, M, 1] int32 argmin row within batch g)."""
    G, M, D = a.shape
    N = b.shape[1]
    grid = (G, M // block_m, N // block_n)
    return pl.pallas_call(
        functools.partial(_row_min_batch_kernel, block_n=block_n),
        name="row_min_batch",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_m, D), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, block_n, D), lambda g, i, j: (g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_m, 1), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, block_m, 1), lambda g, i, j: (g, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((G, M, 1), jnp.float32),
            jax.ShapeDtypeStruct((G, M, 1), jnp.int32),
        ],
        interpret=interpret,
    )(a, b)
