"""jit'd public wrappers around the Pallas kernels.

Padding / masking policy
------------------------
The kernels require MXU-aligned shapes (rows % 128 == 0, feature dim ==
128 lanes).  The wrappers here pad:

* A-rows: zero-padded; callers receive `[M]` results sliced back.
* B-rows: padded with ``FAR`` coordinates so padded points can never
  satisfy a distance predicate (same convention as the device DBSCAN
  pipeline); an explicit ``valid_b`` mask folds into the same mechanism.
* feature dim: zero-padded to 128 (distances unchanged).

The batched wrappers (``eps_count_batch`` / ``row_min_batch``) apply the
identical policy per batch slot: a per-row ``valid_b`` [B, N] mask is
folded into FAR coordinates, row padding is batched, and a row whose
*every* b-point is masked/padded reports ``(inf, -1)`` -- the squared
distance to a FAR point exceeds ``FAR_D2`` (1e29), far above any real
distance, which is how "no valid candidate" is detected after the kernel
(the kernel itself never sees a mask).

Platform dispatch: on TPU the batched kernels compile natively
(MXU-tiled).  Elsewhere they run as a *tiled jnp loop* over b-tiles --
the same blocking as the kernels, expressed as ``lax.while_loop`` so the
trip count is data-dependent: the loop stops at the last tile holding a
valid candidate (static padding up to the candidate cap is never
scanned) and, for ``eps_count_batch(stop_at=k)``, as soon as every
valid a-row has accumulated ``k`` hits -- the paper's offset-ascending
early termination, which a one-shot broadcast cannot express.
``interpret=True`` forces the Pallas kernels under the interpreter
(slow; kernel parity tests only).  The unbatched wrappers keep their
historical behaviour of interpreting on non-TPU backends.  Set
``repro.kernels.ops.FORCE_REF = True`` to route everything through
``ref.py``.

``stop_at`` contract: with ``stop_at=k`` the returned counts satisfy
``min(count, k) == min(exact_count, k)`` (values below k are exact;
values >= k mean "at least k" and may undercount the exact total).
Thresholding at ``>= k`` -- the only thing core identification does --
is therefore exact.  The TPU kernels simply return full counts, which
satisfies the contract trivially.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro import obs

from . import ref
from .pairwise import (eps_count_pallas, row_min_pallas,
                       eps_count_batch_pallas, row_min_batch_pallas,
                       eps_count_band_batch_pallas, row_min2_batch_pallas,
                       LANE)
from .flash_attention import flash_attention_pallas

FAR = 1e15
# any squared distance >= FAR_D2 can only involve a FAR-padded/masked
# point (real coordinates are orders of magnitude below FAR), so it
# marks "no valid candidate" after a row_min kernel
FAR_D2 = 1e29
FORCE_REF = False
# REPRO_FORCE_INTERPRET=1 routes the batched wrappers through the
# *Pallas kernels under the interpreter* on non-TPU backends (instead
# of the tiled jnp fast path) -- how a CPU-only CI runner exercises the
# exact kernel code the device serving path compiles on TPU.  Read at
# import; per-call ``interpret=`` arguments still take precedence.
FORCE_INTERPRET = os.environ.get("REPRO_FORCE_INTERPRET", "") not in ("", "0")


def interpret_default(interpret: Optional[bool]) -> Optional[bool]:
    """Resolve a caller's ``interpret=None`` against the
    ``REPRO_FORCE_INTERPRET`` knob (module docstring)."""
    if interpret is None and FORCE_INTERPRET:
        return True
    return interpret


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_rows(x: jnp.ndarray, mult: int, fill: float,
              axis: int = 0) -> jnp.ndarray:
    """Pad ``axis`` up to a multiple of ``mult`` with ``fill``."""
    m = x.shape[axis]
    tgt = ((m + mult - 1) // mult) * mult
    if tgt == m:
        return x
    shape = list(x.shape)
    shape[axis] = tgt - m
    return jnp.concatenate([x, jnp.full(shape, fill, x.dtype)], axis=axis)


def _pad_feat(x: jnp.ndarray, lane: int = LANE) -> jnp.ndarray:
    """Zero-pad the (last) feature axis to the lane width."""
    d = x.shape[-1]
    if d == lane:
        return x
    if d > lane:
        raise ValueError(f"feature dim {d} > lane width {lane}")
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, lane - d)])


@functools.partial(jax.jit, static_argnames=("block_m", "block_n"))
def eps_count(a: jnp.ndarray, b: jnp.ndarray, eps,
              valid_b: Optional[jnp.ndarray] = None,
              *, block_m: int = 128, block_n: int = 128) -> jnp.ndarray:
    """Count of b-points within ``eps`` of each a-point. Returns [M] int32."""
    if FORCE_REF:
        return ref.eps_count(a, b, eps, valid_b)
    M = a.shape[0]
    a32 = a.astype(jnp.float32)
    b32 = b.astype(jnp.float32)
    if valid_b is not None:
        b32 = jnp.where(valid_b[:, None], b32, FAR)
    ap = _pad_feat(_pad_rows(a32, block_m, 0.0))
    bp = _pad_feat(_pad_rows(b32, block_n, FAR))
    eps2 = jnp.asarray(eps, jnp.float32) ** 2
    out = eps_count_pallas(ap, bp, eps2, block_m=block_m, block_n=block_n,
                           interpret=_interpret())
    return out[:M, 0]


@functools.partial(jax.jit, static_argnames=("block_m", "block_n"))
def row_min(a: jnp.ndarray, b: jnp.ndarray,
            valid_b: Optional[jnp.ndarray] = None,
            *, block_m: int = 128, block_n: int = 128):
    """Per-row (min squared distance, argmin) into b. Returns ([M], [M]).

    A row with no valid b-point at all (every candidate masked by
    ``valid_b``) reports ``(inf, -1)``, never an in-range index into a
    masked row -- the distance to a FAR-folded point exceeds ``FAR_D2``,
    which is the post-kernel detection threshold."""
    if FORCE_REF:
        return ref.row_min(a, b, valid_b)
    M = a.shape[0]
    a32 = a.astype(jnp.float32)
    b32 = b.astype(jnp.float32)
    if valid_b is not None:
        b32 = jnp.where(valid_b[:, None], b32, FAR)
    ap = _pad_feat(_pad_rows(a32, block_m, 0.0))
    bp = _pad_feat(_pad_rows(b32, block_n, FAR))
    mins, args = row_min_pallas(ap, bp, block_m=block_m, block_n=block_n,
                                interpret=_interpret())
    mins, args = mins[:M, 0], args[:M, 0]
    none = mins >= FAR_D2
    return (jnp.where(none, jnp.inf, mins),
            jnp.where(none, jnp.int32(-1), args))


# --------------------------------------------------------------------------
# batched (leading grid-batch dimension) wrappers
# --------------------------------------------------------------------------

def _use_batch_pallas(interpret) -> bool:
    """Dispatch policy for the batched wrappers (module docstring):
    native Pallas on TPU, the tiled jnp loop elsewhere, unless the
    caller forces the interpreter (parity tests) or native
    compilation."""
    if FORCE_REF:
        return False
    if interpret is None:
        return jax.default_backend() == "tpu"
    return True


def _tile_prep(b32, valid_b, block_n):
    """Pad the candidate axis to a tile multiple and return (b tiles,
    valid tiles, index of the last tile holding any valid candidate)."""
    B, N = b32.shape[0], b32.shape[1]
    if valid_b is None:
        valid_b = jnp.ones((B, N), bool)
    bp = _pad_rows(b32, block_n, FAR, axis=1)
    vp = jnp.concatenate(
        [valid_b, jnp.zeros((B, bp.shape[1] - N), bool)], axis=1) \
        if bp.shape[1] != N else valid_b
    # 1 + the highest valid slot, in tiles: the loop never scans the
    # all-padding tail that static caps force onto the candidate axis
    last = jnp.max(jnp.where(vp, jnp.arange(vp.shape[1])[None, :] + 1, 0))
    n_tiles = (last + block_n - 1) // block_n
    return bp, vp, n_tiles


def _eps_count_tiled(a32, b32, eps2, valid_a, valid_b, stop_at, block_n):
    """Non-TPU fast path: b-tile loop with data-dependent trip count
    (see module docstring).  Each tile is the fused broadcast form --
    the optimal XLA-CPU shape -- so the win over the one-shot broadcast
    is pure work skipped, not a different contraction."""
    B, M, _ = a32.shape
    bp, vp, n_tiles = _tile_prep(b32, valid_b, block_n)
    if valid_a is None:
        valid_a = jnp.ones((B, M), bool)

    def cond(state):
        t, cnt = state
        live = t < n_tiles
        if stop_at is not None:
            live = live & jnp.any((cnt < stop_at) & valid_a)
        return live

    def body(state):
        t, cnt = state
        bt = jax.lax.dynamic_slice_in_dim(bp, t * block_n, block_n, axis=1)
        vt = jax.lax.dynamic_slice_in_dim(vp, t * block_n, block_n, axis=1)
        d2 = jnp.sum((a32[:, :, None, :] - bt[:, None, :, :]) ** 2, axis=-1)
        hit = (d2 <= eps2) & vt[:, None, :]
        return t + 1, cnt + hit.sum(axis=2, dtype=jnp.int32)

    _, cnt = jax.lax.while_loop(
        cond, body, (jnp.int32(0), jnp.zeros((B, M), jnp.int32)))
    return cnt


def _row_min_tiled(a32, b32, valid_b, block_n):
    """Non-TPU fast path for the nearest query: same b-tile loop; no
    stop condition (the minimum needs every valid candidate) but the
    padding tail is still skipped."""
    B, M, _ = a32.shape
    bp, vp, n_tiles = _tile_prep(b32, valid_b, block_n)

    def body(state):
        t, best_d, best_i = state
        bt = jax.lax.dynamic_slice_in_dim(bp, t * block_n, block_n, axis=1)
        vt = jax.lax.dynamic_slice_in_dim(vp, t * block_n, block_n, axis=1)
        d2 = jnp.sum((a32[:, :, None, :] - bt[:, None, :, :]) ** 2, axis=-1)
        d2 = jnp.where(vt[:, None, :], d2, jnp.inf)
        tmin = jnp.min(d2, axis=2)
        targ = jnp.argmin(d2, axis=2).astype(jnp.int32) + t * block_n
        better = tmin < best_d
        return (t + 1, jnp.where(better, tmin, best_d),
                jnp.where(better, targ, best_i))

    _, mins, args = jax.lax.while_loop(
        lambda s: s[0] < n_tiles, body,
        (jnp.int32(0), jnp.full((B, M), jnp.inf, jnp.float32),
         jnp.full((B, M), -1, jnp.int32)))
    return mins, args


@functools.partial(jax.jit, static_argnames=(
    "block_m", "block_n", "interpret", "stop_at"))
def eps_count_batch(a: jnp.ndarray, b: jnp.ndarray, eps,
                    valid_b: Optional[jnp.ndarray] = None,
                    valid_a: Optional[jnp.ndarray] = None,
                    *, block_m: int = 128, block_n: int = 128,
                    interpret: Optional[bool] = None,
                    stop_at: Optional[int] = None) -> jnp.ndarray:
    """Batched eps-counts: a [B, M, d], b [B, N, d], valid_b [B, N].

    Returns [B, M] int32 counts of valid b-rows of batch slot g within
    ``eps`` of each a-row of slot g.  ``stop_at`` enables the saturating
    early-exit contract (module docstring); ``valid_a`` only feeds that
    exit decision -- invalid a-rows still receive (garbage) counts the
    caller masks."""
    eps2 = jnp.asarray(eps, jnp.float32) ** 2
    a32 = a.astype(jnp.float32)
    b32 = b.astype(jnp.float32)
    if not _use_batch_pallas(interpret):
        if FORCE_REF:
            return ref.eps_count_batch(a32, b32, eps, valid_b)
        return _eps_count_tiled(a32, b32, eps2, valid_a, valid_b,
                                stop_at, block_n)
    if valid_b is not None:
        b32 = jnp.where(valid_b[:, :, None], b32, FAR)
    M = a.shape[1]
    ap = _pad_feat(_pad_rows(a32, block_m, 0.0, axis=1))
    bp = _pad_feat(_pad_rows(b32, block_n, FAR, axis=1))
    out = eps_count_batch_pallas(ap, bp, eps2, block_m=block_m,
                                 block_n=block_n,
                                 interpret=bool(interpret))
    return out[:, :M, 0]


@functools.partial(jax.jit,
                   static_argnames=("block_m", "block_n", "interpret"))
def row_min_batch(a: jnp.ndarray, b: jnp.ndarray,
                  valid_b: Optional[jnp.ndarray] = None,
                  *, block_m: int = 128, block_n: int = 128,
                  interpret: Optional[bool] = None):
    """Batched :func:`row_min`: a [B, M, d], b [B, N, d], valid_b [B, N].

    Returns ([B, M] f32 min squared distance, [B, M] int32 argmin into
    slot g's b-rows); a row with no valid candidate reports
    ``(inf, -1)``."""
    a32 = a.astype(jnp.float32)
    b32 = b.astype(jnp.float32)
    if not _use_batch_pallas(interpret):
        if FORCE_REF:
            return ref.row_min_batch(a32, b32, valid_b)
        return _row_min_tiled(a32, b32, valid_b, block_n)
    if valid_b is not None:
        b32 = jnp.where(valid_b[:, :, None], b32, FAR)
    M = a.shape[1]
    ap = _pad_feat(_pad_rows(a32, block_m, 0.0, axis=1))
    bp = _pad_feat(_pad_rows(b32, block_n, FAR, axis=1))
    mins, args = row_min_batch_pallas(ap, bp, block_m=block_m,
                                      block_n=block_n,
                                      interpret=bool(interpret))
    mins, args = mins[:, :M, 0], args[:, :M, 0]
    none = mins >= FAR_D2
    return (jnp.where(none, jnp.inf, mins),
            jnp.where(none, jnp.int32(-1), args))


def _eps_count_band_tiled(a32, b32, lo2, hi2, stop_row, valid_b, block_n):
    """Non-TPU fast path of :func:`eps_count_band_batch`: one b-tile
    loop accumulating both thresholds' counts.  ``stop_row`` ([B, M]
    int32 or None) is the per-row saturation bar on the *lo* count --
    the delta engine's MinPts-minus-own-count early exit; rows whose
    final lo-count is below their bar have provably scanned every valid
    tile, so their hi-count is complete (see the wrapper contract)."""
    B, M, _ = a32.shape
    bp, vp, n_tiles = _tile_prep(b32, valid_b, block_n)

    def cond(state):
        t, lo, hi = state
        live = t < n_tiles
        if stop_row is not None:
            live = live & jnp.any(lo < stop_row)
        return live

    def body(state):
        t, lo, hi = state
        bt = jax.lax.dynamic_slice_in_dim(bp, t * block_n, block_n, axis=1)
        vt = jax.lax.dynamic_slice_in_dim(vp, t * block_n, block_n, axis=1)
        d2 = jnp.sum((a32[:, :, None, :] - bt[:, None, :, :]) ** 2, axis=-1)
        d2 = jnp.where(vt[:, None, :], d2, jnp.inf)
        return (t + 1,
                lo + (d2 <= lo2).sum(axis=2, dtype=jnp.int32),
                hi + (d2 <= hi2).sum(axis=2, dtype=jnp.int32))

    z = jnp.zeros((B, M), jnp.int32)
    _, lo, hi = jax.lax.while_loop(cond, body, (jnp.int32(0), z, z))
    return lo, hi


def _row_min2_tiled(a32, b32, valid_b, block_n):
    """Non-TPU fast path of :func:`row_min2_batch`: the ``_row_min_tiled``
    loop extended with the runner-up merge (smaller of both tiles'
    runners-up and the loser of the two firsts)."""
    B, M, _ = a32.shape
    bp, vp, n_tiles = _tile_prep(b32, valid_b, block_n)

    def body(state):
        t, best, best2, arg = state
        bt = jax.lax.dynamic_slice_in_dim(bp, t * block_n, block_n, axis=1)
        vt = jax.lax.dynamic_slice_in_dim(vp, t * block_n, block_n, axis=1)
        d2 = jnp.sum((a32[:, :, None, :] - bt[:, None, :, :]) ** 2, axis=-1)
        d2 = jnp.where(vt[:, None, :], d2, jnp.inf)
        tloc = jnp.argmin(d2, axis=2).astype(jnp.int32)
        tmin = jnp.min(d2, axis=2)
        cols = jnp.arange(d2.shape[2], dtype=jnp.int32)
        d2_wo = jnp.where(cols[None, None, :] == tloc[:, :, None],
                          jnp.inf, d2)
        tmin2 = jnp.min(d2_wo, axis=2)
        better = tmin < best
        loser = jnp.maximum(best, tmin)
        return (t + 1, jnp.where(better, tmin, best),
                jnp.minimum(jnp.minimum(best2, tmin2), loser),
                jnp.where(better, tloc + t * block_n, arg))

    inf = jnp.full((B, M), jnp.inf, jnp.float32)
    _, mins, mins2, args = jax.lax.while_loop(
        lambda s: s[0] < n_tiles, body,
        (jnp.int32(0), inf, inf, jnp.full((B, M), -1, jnp.int32)))
    return mins, mins2, args


@functools.partial(jax.jit, static_argnames=(
    "block_m", "block_n", "interpret", "has_stop"))
def _eps_count_band_batch_jit(a, b, eps_lo, eps_hi, valid_b, stop_row,
                              *, block_m, block_n, interpret, has_stop):
    lo2 = jnp.asarray(eps_lo, jnp.float32) ** 2
    hi2 = jnp.asarray(eps_hi, jnp.float32) ** 2
    a32 = a.astype(jnp.float32)
    b32 = b.astype(jnp.float32)
    if not _use_batch_pallas(interpret):
        if FORCE_REF:
            return ref.eps_count_band_batch(a32, b32, eps_lo, eps_hi,
                                            valid_b)
        return _eps_count_band_tiled(a32, b32, lo2, hi2,
                                     stop_row if has_stop else None,
                                     valid_b, block_n)
    if valid_b is not None:
        b32 = jnp.where(valid_b[:, :, None], b32, FAR)
    M = a.shape[1]
    ap = _pad_feat(_pad_rows(a32, block_m, 0.0, axis=1))
    bp = _pad_feat(_pad_rows(b32, block_n, FAR, axis=1))
    lo, hi = eps_count_band_batch_pallas(
        ap, bp, jnp.stack([lo2, hi2]), block_m=block_m, block_n=block_n,
        interpret=bool(interpret))
    return lo[:, :M, 0], hi[:, :M, 0]


def eps_count_band_batch(a, b, eps_lo, eps_hi,
                         valid_b: Optional[jnp.ndarray] = None,
                         stop_row: Optional[jnp.ndarray] = None,
                         *, block_m: int = 128, block_n: int = 128,
                         interpret: Optional[bool] = None):
    """Two-threshold batched eps-counts (a [B, M, d], b [B, N, d]).

    Returns ``(count_lo, count_hi)`` [B, M] int32 -- hits at
    ``d2 <= eps_lo**2`` and ``d2 <= eps_hi**2`` in one sweep over the
    same distance tiles.  The guard-band serving path brackets the
    exact float64 count between the two whenever the f32 error of the
    decided distances is inside the band.

    ``stop_row`` ([B, M] int32) is a per-row saturating bar on the *lo*
    count (the MinPts-minus-base early exit; pass 0 to exempt padded
    rows).  Contract: a row whose returned ``count_lo`` is below its
    bar has scanned every valid candidate -- its counts are complete --
    because the loop only exits early once *every* row reached its bar.
    The TPU kernel scans everything, satisfying the contract trivially.
    """
    if stop_row is None:
        stop = jnp.zeros((a.shape[0], a.shape[1]), jnp.int32)
        has_stop = False
    else:
        stop, has_stop = stop_row, True
    return _eps_count_band_batch_jit(
        a, b, eps_lo, eps_hi, valid_b, stop, block_m=block_m,
        block_n=block_n, interpret=interpret_default(interpret),
        has_stop=has_stop)


@functools.partial(jax.jit,
                   static_argnames=("block_m", "block_n", "interpret"))
def _row_min2_batch_jit(a, b, valid_b, *, block_m, block_n, interpret):
    a32 = a.astype(jnp.float32)
    b32 = b.astype(jnp.float32)
    if not _use_batch_pallas(interpret):
        if FORCE_REF:
            return ref.row_min2_batch(a32, b32, valid_b)
        return _row_min2_tiled(a32, b32, valid_b, block_n)
    if valid_b is not None:
        b32 = jnp.where(valid_b[:, :, None], b32, FAR)
    M = a.shape[1]
    ap = _pad_feat(_pad_rows(a32, block_m, 0.0, axis=1))
    bp = _pad_feat(_pad_rows(b32, block_n, FAR, axis=1))
    mins, mins2, args = row_min2_batch_pallas(
        ap, bp, block_m=block_m, block_n=block_n,
        interpret=bool(interpret))
    mins, mins2, args = mins[:, :M, 0], mins2[:, :M, 0], args[:, :M, 0]
    none = mins >= FAR_D2
    return (jnp.where(none, jnp.inf, mins),
            jnp.where(mins2 >= FAR_D2, jnp.inf, mins2),
            jnp.where(none, jnp.int32(-1), args))


@jax.jit
def _pairwise_d2_flat_jit(points_res, qa, rr, qo, av):
    # one 1-D gather per coordinate: gathering [T, d] rows makes the TPU
    # lay each out lane-padded, 8.9 GB of temporaries at T = 2^24
    # against 0.5 GB this way
    d2 = jnp.zeros(rr.shape, jnp.float32)
    for k in range(points_res.shape[1]):
        diff = (points_res[:, k][rr] - av[:, k]) - qa[:, k][qo]
        d2 = d2 + diff * diff
    return d2


def pairwise_d2_flat(points_res, qa, rr, qo, av):
    """Flat ragged candidate distances: [T] float32 squared distances.

    The padded-chunk form (``row_min2_batch``) pays pow2 padding plus
    one dispatch per chunk; this op takes the ragged candidate list
    *flat* -- one dispatch, zero padding waste, all the O(T*d) distance
    math on device.  ``points_res`` is the [row_cap, d] float32
    resident buffer; ``rr``/``qo`` [T] int32 give each flat element's
    resident row and query slot; ``qa`` [m, d] float32 holds
    anchor-centered queries and ``av`` [T, d] each element's cell
    anchor (host-gathered -- shipping it per element keeps the jit key
    a function of the T bucket alone, so recompiles converge fast), so
    the subtraction runs on stencil-scale coordinates (same error
    budget as the chunked kernels).  The caller reduces the returned
    distances per segment (segmented min is O(T) and memory-bound;
    XLA's scatter-based segment ops lose to a single host
    ``minimum.reduceat`` pass on CPU, so the reduce stays with the
    caller).  Pure jnp (gather + map): XLA-native on every backend, so
    there is no pallas/interpret variant.
    """
    obs.counter("kernels.dispatch.pairwise_d2_flat").inc()
    return _pairwise_d2_flat_jit(points_res, qa, rr, qo, av)


@jax.jit
def _pairwise_d2_flat_res_jit(points_res, ra, rb, av):
    a = points_res[ra] - av
    b = points_res[rb] - av
    diff = a - b
    return jnp.sum(diff * diff, axis=1)


def pairwise_d2_flat_res(points_res, ra, rb, av):
    """``pairwise_d2_flat`` with *both* operands resident.

    ``ra``/``rb`` [T] int32 pick the two resident rows of each flat
    element; ``av`` [T, d] float32 is each element's cell anchor
    (host-gathered, same jit-key rationale as ``pairwise_d2_flat``).
    Both sides are re-centered by the same resident-row-minus-anchor
    subtract, so the float32 distances carry the established
    stencil-scale error budget.  Used by the delta engine's flat
    core-recount / merge-decide / border stages, where every operand
    already lives in the resident buffer.
    """
    obs.counter("kernels.dispatch.pairwise_d2_flat_res").inc()
    return _pairwise_d2_flat_res_jit(points_res, ra, rb, av)


def row_min2_batch(a, b, valid_b: Optional[jnp.ndarray] = None,
                   *, block_m: int = 128, block_n: int = 128,
                   interpret: Optional[bool] = None):
    """Batched (min, runner-up, argmin) squared distances.

    a [B, M, d], b [B, N, d], valid_b [B, N] -> ([B, M] f32 min d2,
    [B, M] f32 second-smallest slot d2, [B, M] int32 argmin).  The
    runner-up is over remaining slots (a duplicate distance counts),
    so ``min2 - min`` lower-bounds the argmin's margin: wider than the
    f32 error band proves the float64 argmin picks the same row.  No
    valid candidate -> (inf, inf, -1); exactly one -> (d2, inf, idx).
    """
    return _row_min2_batch_jit(a, b, valid_b, block_m=block_m,
                               block_n=block_n,
                               interpret=interpret_default(interpret))


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "scale", "block_q", "block_k"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128) -> jnp.ndarray:
    """Blocked attention. q: [B, H, Sq, D]; k/v: [B, H, Sk, D] (H already
    broadcast over kv groups). Pads Sq/Sk to block multiples internally."""
    if FORCE_REF:
        return ref.mha(q, k, v, causal=causal, window=window,
                       softcap=softcap, scale=scale)
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    qf = q.reshape(B * H, Sq, D)
    kf = k.reshape(B * H, Sk, D)
    vf = v.reshape(B * H, Sk, D)

    def pad_seq(x, blk, fill):
        s = x.shape[1]
        tgt = ((s + blk - 1) // blk) * blk
        if tgt == s:
            return x
        return jnp.concatenate(
            [x, jnp.full((x.shape[0], tgt - s, D), fill, x.dtype)], axis=1)

    qf = pad_seq(qf, block_q, 0.0)
    kf = pad_seq(kf, block_k, 0.0)
    vf = pad_seq(vf, block_k, 0.0)
    # padded queries sit at positions >= Sq and are sliced off; padded keys
    # are masked via sk_actual.
    out = flash_attention_pallas(
        qf, kf, vf, causal=causal, window=window, softcap=softcap,
        scale=scale, sk_actual=Sk, q_offset=Sk - Sq,
        block_q=block_q, block_k=block_k, interpret=_interpret())
    return out[:, :Sq].reshape(B, H, Sq, D)
