"""FastMerging (paper §4.3, Algorithms 4-5).

Decides exactly whether ``MinDist(s_i, s_j) <= eps`` while pruning
distance work via two spatial strategies:

* triangle-inequality pruning: with pivot ``p`` and its nearest point
  ``q`` in the other set at distance > eps, every ``x`` with
  ``dist(x, p) < dist(p, q) - eps`` can never reach the other set.
* angle pruning (Theorem 1): every ``x`` whose angle to ``pq`` exceeds
  ``lambda = max_y [ arcsin(eps / dist(p, y)) + angle(pq, py) ]``
  is provably outside every ``N_eps(y)``;  Theorem 1 guarantees
  ``lambda < 5*pi/6`` for neighboring core grids, so the pruned region
  is never empty and the loop always progresses.

Three engines, identical decisions:

* ``fast_merging``        -- host, paper-faithful (physical point removal).
* ``fast_merging_masked`` -- pure-jnp, removal -> mask update, fixed
                             shapes, ``lax.while_loop`` over the paper's
                             kappa iterations. vmap-able across grid pairs.
* ``center_prune_merge``  -- the KNN-BLOCK-DBSCAN-style baseline the paper
                             compares against in §4.3.1 (single
                             center-distance filter, then brute force).

All report the number of iterations (paper's kappa) and distance
evaluations, the counts behind the paper's efficiency story.
"""

from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

_INF = np.float64(np.inf)


# --------------------------------------------------------------------------
# host, paper-faithful
# --------------------------------------------------------------------------

def _prune(si: np.ndarray, sj: np.ndarray, p: np.ndarray, q: np.ndarray,
           eps: float) -> np.ndarray:
    """Algorithm 4: remove trivial points from ``si`` (returns kept rows)."""
    dpq = np.linalg.norm(p - q)
    sigma = dpq - eps
    # lambda = max_y arcsin(eps/d(p,y)) + angle(pq, py)   (eq. 5, eq. 10)
    py = sj - p[None, :]
    dpy = np.linalg.norm(py, axis=1)
    # all y satisfy d(p,y) >= d(p,q) > eps  (q is the argmin), so arcsin is safe
    cos_t1 = np.clip((py @ (q - p)) / (dpy * dpq), -1.0, 1.0)
    lam = float(np.max(np.arcsin(np.clip(eps / dpy, -1.0, 1.0)) + np.arccos(cos_t1)))

    px = si - p[None, :]
    dpx = np.linalg.norm(px, axis=1)
    tri = dpx < sigma                                   # triangle-inequality prune
    with np.errstate(invalid="ignore", divide="ignore"):
        cos_g = np.clip((px @ (q - p)) / (dpx * dpq), -1.0, 1.0)
        theta = np.arccos(cos_g)
    theta = np.where(dpx == 0.0, 0.0, theta)            # x == p handled by tri
    ang = theta > lam                                   # angle prune
    return si[~(tri | ang)]


def fast_merging(si: np.ndarray, sj: np.ndarray, eps: float,
                 rng: np.random.Generator | None = None,
                 stats: dict | None = None) -> bool:
    """Algorithm 5 (host). Exact: True iff MinDist(si, sj) <= eps."""
    si = np.asarray(si, np.float64).copy()
    sj = np.asarray(sj, np.float64).copy()
    if si.size == 0 or sj.size == 0:
        return False
    eps = float(eps)
    idx = 0 if rng is None else int(rng.integers(len(si)))
    p = si[idx]
    iters = 0
    dist_evals = 0
    while True:
        iters += 1
        # q = argmin_{y in s_j} dist(p, y)
        dj = np.linalg.norm(sj - p[None, :], axis=1)
        dist_evals += len(sj)
        jq = int(np.argmin(dj))
        q = sj[jq]
        if dj[jq] <= eps:
            break_yes = True
            break
        si = _prune(si, sj, p, q, eps)
        dist_evals += len(si)
        if len(si) == 0:
            break_yes = False
            break
        # p = argmin_{x in s_i} dist(x, q)
        di = np.linalg.norm(si - q[None, :], axis=1)
        dist_evals += len(si)
        ip = int(np.argmin(di))
        p = si[ip]
        if di[ip] <= eps:
            break_yes = True
            break
        sj = _prune(sj, si, q, p, eps)
        dist_evals += len(sj)
        if len(sj) == 0:
            break_yes = False
            break
    if stats is not None:
        stats["iters"] = stats.get("iters", 0) + iters
        stats["max_iters"] = max(stats.get("max_iters", 0), iters)
        stats["dist_evals"] = stats.get("dist_evals", 0) + dist_evals
        stats["calls"] = stats.get("calls", 0) + 1
    return break_yes


def brute_min_dist(si: np.ndarray, sj: np.ndarray) -> float:
    """O(m_i * m_j) oracle for MinDist (paper §4.3.1 'straightforward way')."""
    d2 = ((si[:, None, :] - sj[None, :, :]) ** 2).sum(-1)
    return float(np.sqrt(d2.min()))


def center_prune_merge(si: np.ndarray, sj: np.ndarray, eps: float,
                       stats: dict | None = None) -> bool:
    """KNN-BLOCK-DBSCAN-style merging baseline (paper §4.3.1).

    Prunes p in s_i with dist(p, c_j) > eps + xi_j (and symmetrically),
    then brute-forces the rest.  Exact, but degrades to O(m_i m_j).
    """
    si = np.asarray(si, np.float64)
    sj = np.asarray(sj, np.float64)
    ci, cj = si.mean(0), sj.mean(0)
    xi_i = np.linalg.norm(si - ci[None], axis=1).max()
    xi_j = np.linalg.norm(sj - cj[None], axis=1).max()
    keep_i = np.linalg.norm(si - cj[None], axis=1) <= eps + xi_j
    keep_j = np.linalg.norm(sj - ci[None], axis=1) <= eps + xi_i
    a, b = si[keep_i], sj[keep_j]
    if stats is not None:
        stats["dist_evals"] = stats.get("dist_evals", 0) + \
            len(si) + len(sj) + len(a) * len(b)
        stats["calls"] = stats.get("calls", 0) + 1
    if len(a) == 0 or len(b) == 0:
        return False
    return brute_min_dist(a, b) <= eps


# --------------------------------------------------------------------------
# device, masked (removal -> mask update), fixed shapes
# --------------------------------------------------------------------------

def _masked_prune_jnp(A, va, B, vb, p, q, eps):
    """Algorithm 4 on masks: returns updated validity mask for A.

    The angular test runs entirely in cosine space: with
    ``lam_y = arcsin(eps/d(p,y)) + arccos(cos_b)`` and
    ``theta_x = arccos(cos_g)`` all in [0, pi] where cosine is strictly
    decreasing, ``theta_x > max_y lam_y`` is equivalent to
    ``cos_g < min_y cos(lam_y)`` with
    ``cos(a + b) = cos_a cos_b - sin_a sin_b`` (sum identity), unless
    some ``lam_y`` exceeds pi -- detected as ``cos_b < -cos_a`` (since
    ``a <= pi/2``), in which case ``lam >= pi >= theta`` and no point
    is angle-pruned.  This removes every ``arcsin``/``arccos`` from
    the merge hot loop (they dominated its wall on CPU)."""
    dpq = jnp.linalg.norm(p - q)
    sigma = dpq - eps
    # full-f32 dots: a TPU computes a default-precision f32 dot in bf16
    # passes, which would perturb the cosines past the prune's margins
    hi = jax.lax.Precision.HIGHEST
    py = B - p[None, :]
    dpy = jnp.linalg.norm(py, axis=1)
    safe_dpy = jnp.maximum(dpy, 1e-30)
    cos_b = jnp.clip(jnp.dot(py, q - p, precision=hi)
                     / (safe_dpy * jnp.maximum(dpq, 1e-30)), -1., 1.)
    sin_a = jnp.clip(eps / safe_dpy, 0., 1.)
    cos_a = jnp.sqrt(1. - sin_a * sin_a)
    sin_b = jnp.sqrt(1. - cos_b * cos_b)
    cos_ab = cos_a * cos_b - sin_a * sin_b
    over_pi = jnp.any(vb & (cos_b < -cos_a))
    # empty B: min over nothing -> +inf, so every x is angle-pruned
    # (matching the lam = -inf behavior of the angle-space form)
    cos_lam = jnp.min(jnp.where(vb, cos_ab, jnp.inf))

    px = A - p[None, :]
    dpx = jnp.linalg.norm(px, axis=1)
    tri = dpx < sigma
    cos_g = jnp.clip(jnp.dot(px, q - p, precision=hi) /
                     (jnp.maximum(dpx, 1e-30) * jnp.maximum(dpq, 1e-30)), -1., 1.)
    cos_g = jnp.where(dpx == 0.0, 1.0, cos_g)   # theta(p) = 0
    ang = (cos_g < cos_lam) & ~over_pi
    return va & ~(tri | ang)


@partial(jax.jit, static_argnames=("max_iters",))
def fast_merging_masked(si: jnp.ndarray, valid_i: jnp.ndarray,
                        sj: jnp.ndarray, valid_j: jnp.ndarray,
                        eps, max_iters: int = 64):
    """Algorithm 5 with masking. Exact decision; fixed shapes.

    Args:
      si: [Mi, d] padded point set, valid_i: [Mi] bool.
      sj: [Mj, d] padded point set, valid_j: [Mj] bool.
    Returns:
      (merge: bool, iters: int32) -- `iters` is the paper's kappa.
    """
    si = si.astype(jnp.float32)
    sj = sj.astype(jnp.float32)
    eps = jnp.asarray(eps, jnp.float32)

    def masked_argmin(dists, valid):
        d = jnp.where(valid, dists, jnp.inf)
        i = jnp.argmin(d)
        return i, d[i]

    # pivot: first valid point of s_i
    p0 = jnp.argmax(valid_i)

    def cond(state):
        va, vb, _, done, _, it = state
        return (~done) & jnp.any(va) & jnp.any(vb) & (it < max_iters)

    def body(state):
        va, vb, p_idx, done, res, it = state
        p = si[p_idx]
        jq, dq = masked_argmin(jnp.linalg.norm(sj - p[None], axis=1), vb)
        q = sj[jq]
        hit1 = dq <= eps
        va_pruned = _masked_prune_jnp(si, va, sj, vb, p, q, eps)
        va2 = jnp.where(hit1, va, va_pruned)
        empty_i = ~jnp.any(va2)
        ip, dp = masked_argmin(jnp.linalg.norm(si - q[None], axis=1), va2)
        hit2 = (~hit1) & (~empty_i) & (dp <= eps)
        p2 = si[ip]
        vb2 = jnp.where(hit1 | hit2 | empty_i, vb,
                        _masked_prune_jnp(sj, vb, si, va2, q, p2, eps))
        new_done = hit1 | hit2 | empty_i | ~jnp.any(vb2)
        new_res = hit1 | hit2
        return (va2, vb2, ip, done | new_done, res | new_res, it + 1)

    init = (valid_i, valid_j, p0, ~(jnp.any(valid_i) & jnp.any(valid_j)),
            jnp.zeros((), bool), jnp.zeros((), jnp.int32))
    va, vb, _, done, res, it = jax.lax.while_loop(cond, body, init)
    return res, it


def fast_merging_batch(si, valid_i, sj, valid_j, eps, max_iters: int = 64):
    """vmap of ``fast_merging_masked`` across a batch of grid pairs."""
    f = partial(fast_merging_masked, max_iters=max_iters)
    return jax.vmap(lambda a, va, b, vb: f(a, va, b, vb, eps))(
        si, valid_i, sj, valid_j)
