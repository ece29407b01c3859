"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantic ground truth: kernel tests sweep shapes/dtypes and
``assert_allclose`` against these functions; the jit'd wrappers in
``ops.py`` fall back to them on platforms without Pallas support.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# --------------------------------------------------------------------------
# pairwise distances (DBSCAN hot spots)
# --------------------------------------------------------------------------

def sq_dists(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """[M, d] x [N, d] -> [M, N] squared Euclidean distances."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    aa = jnp.sum(a * a, axis=1)[:, None]
    bb = jnp.sum(b * b, axis=1)[None, :]
    d2 = aa + bb - 2.0 * jnp.dot(a, b.T, precision=jax.lax.Precision.HIGHEST)
    return jnp.maximum(d2, 0.0)


def eps_count(a: jnp.ndarray, b: jnp.ndarray, eps: jnp.ndarray,
              valid_b: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Per-row count of points of ``b`` within ``eps`` of each row of ``a``."""
    d2 = sq_dists(a, b)
    hit = d2 <= jnp.asarray(eps, jnp.float32) ** 2
    if valid_b is not None:
        hit = hit & valid_b[None, :]
    return hit.sum(axis=1).astype(jnp.int32)


def row_min(a: jnp.ndarray, b: jnp.ndarray,
            valid_b: Optional[jnp.ndarray] = None):
    """Per-row (min squared distance, argmin index) into ``b``.

    Contract for a fully-masked row (no valid b-point at all): the min
    distance is ``inf`` and the argmin is ``-1`` -- never an in-range
    index into masked/padded rows.  ``border_block`` relies on this
    whenever a grid has no core candidates.
    """
    d2 = sq_dists(a, b)
    if valid_b is not None:
        d2 = jnp.where(valid_b[None, :], d2, jnp.inf)
    mins = jnp.min(d2, axis=1)
    idx = jnp.argmin(d2, axis=1).astype(jnp.int32)
    idx = jnp.where(jnp.isinf(mins), jnp.int32(-1), idx)
    return mins, idx


# --------------------------------------------------------------------------
# batched (leading grid-batch dimension) forms
# --------------------------------------------------------------------------

def sq_dists_batch(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """[B, M, d] x [B, N, d] -> [B, M, N] squared Euclidean distances.

    Same `aa + bb - 2ab` matmul form as the Pallas kernels (the MXU
    path), so kernel parity against this oracle is tight."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    aa = jnp.sum(a * a, axis=-1)[:, :, None]
    bb = jnp.sum(b * b, axis=-1)[:, None, :]
    ab = jnp.einsum("bmd,bnd->bmn", a, b,
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)
    return jnp.maximum(aa + bb - 2.0 * ab, 0.0)


def eps_count_batch(a: jnp.ndarray, b: jnp.ndarray, eps: jnp.ndarray,
                    valid_b: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Per-batch per-row eps-counts: a [B, M, d], b [B, N, d], valid_b
    [B, N] -> [B, M] int32."""
    d2 = sq_dists_batch(a, b)
    hit = d2 <= jnp.asarray(eps, jnp.float32) ** 2
    if valid_b is not None:
        hit = hit & valid_b[:, None, :]
    return hit.sum(axis=-1).astype(jnp.int32)


def row_min_batch(a: jnp.ndarray, b: jnp.ndarray,
                  valid_b: Optional[jnp.ndarray] = None):
    """Batched :func:`row_min`: a [B, M, d], b [B, N, d], valid_b [B, N]
    -> ([B, M] f32 min d2, [B, M] int32 argmin; (inf, -1) for rows with
    no valid b-point)."""
    d2 = sq_dists_batch(a, b)
    if valid_b is not None:
        d2 = jnp.where(valid_b[:, None, :], d2, jnp.inf)
    mins = jnp.min(d2, axis=-1)
    idx = jnp.argmin(d2, axis=-1).astype(jnp.int32)
    idx = jnp.where(jnp.isinf(mins), jnp.int32(-1), idx)
    return mins, idx


def eps_count_band_batch(a: jnp.ndarray, b: jnp.ndarray,
                         eps_lo: jnp.ndarray, eps_hi: jnp.ndarray,
                         valid_b: Optional[jnp.ndarray] = None):
    """Two-threshold batched eps-counts: hits at ``d2 <= eps_lo**2`` and
    at ``d2 <= eps_hi**2`` in one pass (a [B, M, d], b [B, N, d] ->
    two [B, M] int32 arrays).

    The guard-band discipline of the device serving path rests on
    ``count_lo <= exact_count <= count_hi`` whenever the float32 error
    of every decided distance is below the lo/hi band, which is how a
    core decision is proven without float64.
    """
    d2 = sq_dists_batch(a, b)
    lo2 = jnp.asarray(eps_lo, jnp.float32) ** 2
    hi2 = jnp.asarray(eps_hi, jnp.float32) ** 2
    hit_lo = d2 <= lo2
    hit_hi = d2 <= hi2
    if valid_b is not None:
        hit_lo = hit_lo & valid_b[:, None, :]
        hit_hi = hit_hi & valid_b[:, None, :]
    return (hit_lo.sum(axis=-1).astype(jnp.int32),
            hit_hi.sum(axis=-1).astype(jnp.int32))


def row_min2_batch(a: jnp.ndarray, b: jnp.ndarray,
                   valid_b: Optional[jnp.ndarray] = None):
    """Batched (min, runner-up min, argmin) squared distances.

    a [B, M, d], b [B, N, d], valid_b [B, N] -> ([B, M] f32 min,
    [B, M] f32 second-smallest, [B, M] int32 argmin).  The runner-up is
    over the remaining *slots* (duplicate distances count separately),
    so ``min2 - min`` bounds how far the argmin is from being tied --
    the device path's argmin-certainty test.  No valid candidate ->
    (inf, inf, -1); exactly one -> (d2, inf, idx).
    """
    d2 = sq_dists_batch(a, b)
    if valid_b is not None:
        d2 = jnp.where(valid_b[:, None, :], d2, jnp.inf)
    mins = jnp.min(d2, axis=-1)
    idx = jnp.argmin(d2, axis=-1).astype(jnp.int32)
    cols = jnp.arange(d2.shape[-1], dtype=jnp.int32)
    d2_wo = jnp.where(cols[None, None, :] == idx[:, :, None], jnp.inf, d2)
    mins2 = jnp.min(d2_wo, axis=-1)
    idx = jnp.where(jnp.isinf(mins), jnp.int32(-1), idx)
    return mins, mins2, idx


def min_dist(a: jnp.ndarray, va: jnp.ndarray,
             b: jnp.ndarray, vb: jnp.ndarray) -> jnp.ndarray:
    """Minimum squared distance between two masked sets (scalar)."""
    d2 = sq_dists(a, b)
    d2 = jnp.where(va[:, None] & vb[None, :], d2, jnp.inf)
    return jnp.min(d2)


# --------------------------------------------------------------------------
# attention (LM hot spot)
# --------------------------------------------------------------------------

def mha(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
        causal: bool = True, window: Optional[int] = None,
        softcap: Optional[float] = None,
        scale: Optional[float] = None) -> jnp.ndarray:
    """Reference multi-head attention.

    q: [B, H, Sq, D], k/v: [B, H, Sk, D] (kv heads already broadcast).
    ``window``: sliding-window width (keys with q_pos - k_pos >= window
    masked out); ``softcap``: gemma2-style tanh logit soft capping.
    Query position i is aligned to key position i + (Sk - Sq) so decode
    (Sq=1) attends to the full prefix.
    """
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    if scale is None:
        scale = D ** -0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        logits = jnp.tanh(logits / softcap) * softcap
    qpos = jnp.arange(Sq)[:, None] + (Sk - Sq)
    kpos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= (qpos - kpos) < window
    logits = jnp.where(mask[None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)
