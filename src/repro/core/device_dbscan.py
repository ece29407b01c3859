"""GriT-DBSCAN fully in-graph (device path).

The whole of Algorithm 6 as one jittable function with static shape caps:

  grids (Alg 1, lax.sort)            -> ``grids.build_grids_device``
  grid-tree neighbor query (Alg 3)   -> ``grid_tree.device_neighbor_table``
  core identification (G13 + all-core shortcut, offset-sorted candidates)
  FastMerging over core-grid pairs (Alg 5, masked)
  connected components (pointer jumping)
  border / noise assignment

Static caps replace the dynamic data structures of the paper; every cap
has an ``overflow`` flag so a driver can retry with larger caps (the
standard static-shape discipline on TPU).

``GritCaps.packed`` (default True) selects *occupancy-packed* dispatch
for the three cap-proportional stages.  The dense strategy maps
``core_block`` / ``border_block`` over every ``grid_cap`` slot and the
merge step over every ``pair_cap`` slot, so work scales with the caps
even when most slots are dead.  The packed strategy keeps the paper's
work-proportional claim: live small grids are compacted to a prefix
sorted by candidate total, and three ``lax.while_loop`` tiers with
data-dependent trip counts sweep that prefix at pow2 sub-caps
(``c_cap/4``, ``c_cap/2``, ``c_cap`` -- the flat pow2-bucket discipline
of ``kernels.ops``), the widest tier doubling as the dense-tail path
for the few heavy grids; merge blocks run only up to the number of
valid pairs.  Outputs are bit-identical to the dense path: a grid in a
tier has candidate total <= the tier width, so no candidate is
truncated, the per-row distance rows are elementwise the same values,
and the result scatters (max for core flags, min for border labels)
are order-independent.  Overflow flags are computed from the global
per-grid candidate totals, never from what a tier dispatched, so the
``OverflowReport`` semantics are unchanged (pinned packed-vs-dense by
``tests/test_packed_dispatch.py``).

``GritCaps.use_kernels`` selects the distance plane for the two
distance-heavy stages.  ``False`` (default) materializes the naive
``[B, P, C, d]`` broadcast difference tensor -- the in-graph oracle.
``True`` routes ``core_block`` (per-point eps-counts over own+neighbor
candidates) through ``kernels.ops.eps_count_batch`` and ``border_block``
(nearest-core-point query) through ``kernels.ops.row_min_batch``: the
MXU-tiled batched Pallas kernels on TPU, a tiled loop with a
data-dependent trip count (padding-tail skip + MinPts early exit)
elsewhere (see the dispatch policy in ``repro.kernels.ops``).  Before a
kernel call both point sets are re-centered on the grid's first own
point: candidates live within the neighbor stencil (a few eps), so the
`aa + bb - 2ab` contraction runs on stencil-scale coordinates and the
cancellation error stays far below the scenario decision margins.  The
overflow flags are computed from candidate totals, never from distance
values, so kernelization leaves the ``OverflowReport`` untouched.

Padding convention: invalid points are moved to ``PAD_COORD`` so they
land in (ignorable) far-away grids and never satisfy a distance
predicate; the kernels share the convention (``kernels.ops.FAR``) for
masked candidate rows.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import ClassVar, Optional, Tuple

import jax
import jax.numpy as jnp

from .grids import build_grids_device, DeviceGrids
from .grid_tree import device_neighbor_table
from .merging import fast_merging_batch
from .labels import label_propagation
from ..kernels import ops as kernel_ops

PAD_COORD = 1e15


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class OverflowReport:
    """Per-cap overflow flags (scalar device bools).

    Each flag names the ``GritCaps`` field (or distributed halo cap) that
    was exceeded, so a driver can grow exactly the caps that overflowed
    instead of blindly scaling everything.  When a flag fires the result
    is a *subset* (silently truncated) and must not be trusted.
    """

    grid: jnp.ndarray        # grid_cap: non-empty grids truncated
    frontier: jnp.ndarray    # frontier_cap: grid-tree level frontier
    neighbors: jnp.ndarray   # k_cap: neighbor grids per grid
    candidates: jnp.ndarray  # c_cap: candidate points per small grid
    core_set: jnp.ndarray    # m_cap: core points per grid (merging)
    pairs: jnp.ndarray       # pair_cap: core-grid merge pairs
    halo: jnp.ndarray        # halo_cap: distributed boundary exchange

    FIELDS: ClassVar[Tuple[str, ...]] = (
        "grid", "frontier", "neighbors", "candidates", "core_set",
        "pairs", "halo")

    def tree_flatten(self):
        return tuple(getattr(self, f) for f in self.FIELDS), None

    @classmethod
    def tree_unflatten(cls, aux, ch):
        return cls(*ch)

    @classmethod
    def none(cls) -> "OverflowReport":
        return cls(*(jnp.zeros((), bool) for _ in cls.FIELDS))

    @classmethod
    def from_vector(cls, vec) -> "OverflowReport":
        assert len(vec) == len(cls.FIELDS)
        return cls(*(vec[i] for i in range(len(cls.FIELDS))))

    def as_vector(self) -> jnp.ndarray:
        return jnp.stack([jnp.asarray(getattr(self, f), bool)
                          for f in self.FIELDS])

    def any(self):
        out = jnp.zeros((), bool)
        for f in self.FIELDS:
            out = out | jnp.asarray(getattr(self, f), bool)
        return out

    def overflowing(self) -> Tuple[str, ...]:
        """Host-side: names of the caps that overflowed."""
        return tuple(f for f in self.FIELDS if bool(getattr(self, f)))

    def __bool__(self) -> bool:
        return bool(self.any())


@dataclasses.dataclass(frozen=True)
class GritCaps:
    """Static shape caps + execution strategy for the in-graph pipeline.

    ``use_kernels`` rides along with the caps (it is part of the same
    static jit key): True routes the core/border distance plane through
    the batched Pallas kernels instead of the naive broadcast tensor.
    """

    grid_cap: int = 1024       # max non-empty grids
    frontier_cap: int = 128    # grid-tree per-level frontier
    k_cap: int = 48            # neighbors per grid
    c_cap: int = 512           # candidate points per grid (self + neighbors)
    m_cap: int = 64            # core points per grid used by merging
    pair_cap: int = 4096       # merge pairs
    grid_block: int = 128      # chunk over grids (memory bound)
    pair_block: int = 512      # chunk over merge pairs
    merge_iters: int = 64      # FastMerging max iterations (paper kappa<=11)
    use_kernels: bool = False  # kernelized distance plane (see module doc)
    packed: bool = True        # occupancy-packed dispatch (see module doc)

    def __post_init__(self):
        # the dense maps reshape [grid_cap] -> [-1, grid_block] and
        # [pair_cap] -> [-1, pair_block]; an indivisible cap used to
        # crash deep inside the pipeline at pg.reshape -- fail loudly
        # at construction instead
        if self.grid_block <= 0 or self.grid_cap % self.grid_block != 0:
            raise ValueError(
                f"grid_cap ({self.grid_cap}) must be a positive multiple "
                f"of grid_block ({self.grid_block})")
        if self.pair_block <= 0 or self.pair_cap % self.pair_block != 0:
            raise ValueError(
                f"pair_cap ({self.pair_cap}) must be a positive multiple "
                f"of pair_block ({self.pair_block})")

    @classmethod
    def for_dim(cls, d: int, **kw) -> "GritCaps":
        """Caps with the frontier sized to the paper's per-level fanout
        bound (2*ceil(sqrt(d))+1)^(d-1) -- a 1.5x memory-term win over a
        generic cap at d=3 (§Perf cluster iterations). Overflow flags
        still guard correctness if data exceeds any cap."""
        import math
        r = 2 * math.ceil(math.sqrt(d)) + 1
        frontier = int(min(r ** max(d - 1, 1), 256))
        kw.setdefault("frontier_cap", max(frontier, 8))
        kw.setdefault("merge_iters", 16)   # paper Remark 3: kappa <= 11
        return cls(**kw)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DeviceDBSCANResult:
    labels: jnp.ndarray        # [n] int32, original order; -1 noise
    core: jnp.ndarray          # [n] bool, original order
    point_grid: jnp.ndarray    # [n] int32 grid row of each point, original
                               # order (rows of the device grid table; f32
                               # identifiers -- provenance, not the float64
                               # host partition)
    num_clusters: jnp.ndarray  # [] int32
    overflow: jnp.ndarray      # [] bool -- any static cap exceeded
    report: OverflowReport     # which cap(s) overflowed
    dispatch_tiers: jnp.ndarray  # [4] int32 dispatch telemetry: grids
                               # swept by the three packed occupancy
                               # tiers (c_cap/4, c_cap/2, c_cap) and, in
                               # slot 3, the dense-path grid slots (0
                               # when packed); their sum is the total
                               # dispatched grid work

    def tree_flatten(self):
        return (self.labels, self.core, self.point_grid, self.num_clusters,
                self.overflow, self.report, self.dispatch_tiers), None

    @classmethod
    def tree_unflatten(cls, aux, ch):
        return cls(*ch)


def _candidates_for_grids(dg: DeviceGrids, nbr: jnp.ndarray, gsel: jnp.ndarray,
                          c_cap: int):
    """Candidate point indices for each grid in ``gsel``: own grid first,
    then neighbors in offset-ascending order (paper's early-exit order).

    The K+1 stencil grids of a row are segments of its ``c_cap`` slots;
    slot s lies in segment ``seg = min(#{k : cum[k] <= s}, K)`` (``cum``
    the inclusive sizes, so ``searchsorted(cum, s, side="right")``), at
    point ``s + starts[grid] - (cum - sizes)[seg]``.  Slots at or past
    the row's total are invalid, with index 0 and the last segment's grid.

    A slot's grid and point shift come from one pass over the K segment
    boundaries, with no gather: ``x[seg] = x[0] + sum_k [cum[k] <= s]
    (x[k+1] - x[k])``, reduced over a major axis so the compares fuse
    into elementwise work and nothing of size [K, B, c_cap] is stored.
    A per-slot binary search would cost ceil(log2(K+1)) rounds of
    batched per-element gathers, which the TPU runs slowly: at d = 3
    (K = 120) they took more device time than every distance.

    Returns (cand_idx [B, c_cap] into sorted points, cand_grid [B, c_cap],
    cand_valid [B, c_cap], cand_total [B])."""
    K = nbr.shape[1]
    cg = jnp.concatenate([gsel[:, None], nbr[gsel]], axis=1)        # [B, K+1]
    cg_valid = cg >= 0
    cgc = jnp.where(cg_valid, cg, 0)
    sizes = jnp.where(cg_valid, dg.counts[cgc], 0)                  # [B, K+1]
    cum = jnp.cumsum(sizes, axis=1)                                 # inclusive
    total = cum[:, -1]
    slots = jnp.arange(c_cap, dtype=jnp.int32)[None, :]             # [1, C]
    # point index minus slot, per segment: the segment's first point less
    # the slots before it
    shift = dg.starts[cgc] - (cum - sizes)                          # [B, K+1]
    past = cum[:, :K].T[:, :, None] <= slots[None]                  # [K, B, C]

    def at_seg(x):
        step = (x[:, 1:] - x[:, :-1]).T[:, :, None]                 # [K, B, 1]
        return x[:, :1] + jnp.sum(jnp.where(past, step, 0), axis=0,
                                  dtype=x.dtype)

    g_of = at_seg(cgc)
    idx = slots + at_seg(shift)
    valid = (slots < total[:, None])
    idx = jnp.where(valid, idx, 0)
    return idx, g_of, valid, total


@partial(jax.jit, static_argnames=("min_pts", "caps"))
def device_dbscan(points: jnp.ndarray, eps, min_pts: int, caps: GritCaps,
                  point_valid: Optional[jnp.ndarray] = None) -> DeviceDBSCANResult:
    """Exact GriT-DBSCAN, fully in-graph. Labels in original point order."""
    n, d = points.shape
    eps = jnp.asarray(eps, points.dtype)
    eps2 = eps * eps
    if point_valid is None:
        point_valid = jnp.ones((n,), bool)
    pts = jnp.where(point_valid[:, None], points, PAD_COORD)

    # ---- step 1: grids + grid tree neighbors --------------------------
    # every phase runs under a ``grit.*`` named scope (the packed tier
    # sweeps under ``<phase>/tier<k>``): HLO op_name metadata that trace
    # viewers and HLO dumps read; codegen and the jit key are unchanged
    with jax.named_scope("grit.grids"):
        dg = build_grids_device(pts, eps, caps.grid_cap)
    with jax.named_scope("grit.neighbors"):
        nbr, nbr_off, ovf_frontier, ovf_k = device_neighbor_table(
            dg.ids, dg.num_grids, frontier_cap=caps.frontier_cap,
            k_cap=caps.k_cap, include_self=False, packed=caps.packed)
    G = caps.grid_cap
    live = jnp.arange(G, dtype=jnp.int32) < dg.num_grids
    sorted_valid = point_valid[dg.order]

    spts = dg.sorted_points

    # ---- step 2: core points ------------------------------------------
    with jax.named_scope("grit.core"):
        # all-core shortcut: grids with >= MinPts (valid) points
        valid_counts = jnp.zeros((G,), jnp.int32).at[dg.point_grid].add(
            sorted_valid.astype(jnp.int32))
        big = (valid_counts >= min_pts) & live
        core_sorted = big[dg.point_grid] & sorted_valid
        # grids holding only padding points (all invalid points share
        # PAD_COORD, so they land in grids of their own) need no core scan
        # and must not count against c_cap
        occupied = live & (valid_counts > 0)

        p_cap = max(min_pts - 1, 1)

        def grid_anchor(gsel):
            """First own point of each selected grid: the re-centering origin
            for the kernelized distance plane (module docstring)."""
            return spts[jnp.minimum(dg.starts[gsel], n - 1)][:, None, :]

        # per-grid candidate totals (own + neighbor occupancies): the same
        # numbers _candidates_for_grids derives per block, computed once for
        # every grid -- they drive the candidates overflow flag and, under
        # packed dispatch, the occupancy-tier assignment
        cg_all = jnp.concatenate(
            [jnp.arange(G, dtype=jnp.int32)[:, None], nbr], axis=1)
        total_all = jnp.sum(
            jnp.where(cg_all >= 0, dg.counts[jnp.maximum(cg_all, 0)], 0),
            axis=1)                                               # [G]
        small_all = (~big) & occupied
        ovf_candidates = jnp.any((total_all > caps.c_cap) & small_all)

        def core_rows(gsel, width, active):
            """Core test of one grid block at candidate width ``width``:
            identical values to the full-width pass for any grid whose
            candidate total fits (no truncation, same candidate prefix
            order, same distance rows)."""
            cand_idx, _, cand_valid, _ = _candidates_for_grids(
                dg, nbr, gsel, width)
            cand_valid = cand_valid & sorted_valid[cand_idx]
            own_slot = jnp.arange(p_cap, dtype=jnp.int32)[None, :]
            own_idx = dg.starts[gsel][:, None] + own_slot
            small = (~big[gsel]) & occupied[gsel] & active
            own_valid = (own_slot < dg.counts[gsel][:, None]) & small[:, None]
            own_idx = jnp.where(own_valid, own_idx, 0)
            a = spts[own_idx]                       # [B, P, d]
            b = spts[cand_idx]                      # [B, C, d]
            if caps.use_kernels:
                # stop_at=min_pts: the saturating-count contract -- exact
                # below min_pts, ">= min_pts" above -- is all the core test
                # needs, and it unlocks the paper's offset-ascending early
                # exit (candidates are already in that order)
                anchor = grid_anchor(gsel)
                cnt = kernel_ops.eps_count_batch(a - anchor, b - anchor, eps,
                                                 valid_b=cand_valid,
                                                 valid_a=own_valid,
                                                 stop_at=min_pts)
            else:
                d2 = jnp.sum((a[:, :, None, :] - b[:, None, :, :]) ** 2,
                             axis=-1)
                hit = (d2 <= eps2) & cand_valid[:, None, :]
                cnt = hit.sum(axis=2)
            return own_idx, (cnt >= min_pts) & own_valid

        GB = caps.grid_block
        if caps.packed:
            # occupancy-packed dispatch: live small grids compacted to a
            # prefix sorted by candidate total (stable, so equal totals keep
            # grid order), swept tier by tier at pow2 sub-caps.  A grid's
            # tier width bounds its candidate total, so every tier sees the
            # exact candidate set; grids whose total exceeds c_cap run (and
            # truncate) in the widest tier exactly as the dense path does,
            # with the candidates flag raised from total_all above.
            tier_w = sorted({max(8, caps.c_cap // 4),
                             max(8, caps.c_cap // 2), caps.c_cap})
            pperm = jnp.argsort(jnp.where(small_all, total_all,
                                          jnp.int32(2 ** 30)), stable=True)
            n_small = jnp.sum(small_all.astype(jnp.int32))
            cuts = [jnp.sum((small_all
                             & (total_all <= w)).astype(jnp.int32))
                    for w in tier_w[:-1]] + [n_small]
            tier_bounds = list(zip([jnp.int32(0)] + cuts[:-1], cuts))
            tier_counts = [hi - lo for lo, hi in tier_bounds]

            def sweep_tiers(row_fn, init, scatter):
                def one_tier(acc, lo, hi, width):
                    nblk = (hi - lo + GB - 1) // GB

                    def body(state):
                        b, acc = state
                        pos = lo + b * GB + jnp.arange(GB, dtype=jnp.int32)
                        active = pos < hi
                        gsel = pperm[jnp.where(active, pos, 0)]
                        oi, val = row_fn(gsel, width, active)
                        return b + 1, scatter(acc, oi, val)

                    return jax.lax.while_loop(
                        lambda s: s[0] < nblk, body, (jnp.int32(0), acc))[1]

                for k, ((lo, hi), width) in enumerate(
                        zip(tier_bounds, tier_w)):
                    with jax.named_scope(f"tier{k + 1}"):
                        init = one_tier(init, lo, hi, width)
                return init

            core_sorted = sweep_tiers(
                core_rows, core_sorted,
                lambda acc, oi, v: acc.at[oi.reshape(-1)].max(v.reshape(-1)))
            dispatch_tiers = jnp.zeros((4,), jnp.int32)
            for t, cnt in enumerate(tier_counts):
                dispatch_tiers = dispatch_tiers.at[t].set(cnt)
        else:
            gsel_all = jnp.arange(G, dtype=jnp.int32).reshape(-1, GB)
            ones = jnp.ones((GB,), bool)
            own_idx, is_core = jax.lax.map(
                lambda gsel: core_rows(gsel, caps.c_cap, ones), gsel_all)
            core_sorted = core_sorted.at[own_idx.reshape(-1)].max(
                is_core.reshape(-1))
            dispatch_tiers = jnp.zeros((4,), jnp.int32).at[3].set(G)

        core_per_grid = jnp.zeros((G,), jnp.int32).at[dg.point_grid].add(
            core_sorted.astype(jnp.int32))
        core_grid = (core_per_grid > 0) & live
        ovf_core_set = jnp.any(core_per_grid > caps.m_cap)

    # ---- step 3: merging -----------------------------------------------
    with jax.named_scope("grit.merge"):
        # pairs (g, g') with g' in Nei(g), both core, deduped by g' > g
        K = caps.k_cap
        gg = jnp.broadcast_to(jnp.arange(G, dtype=jnp.int32)[:, None], (G, K))
        g2 = nbr
        pair_valid = (g2 >= 0) & (g2 > gg) & core_grid[gg] & core_grid[
            jnp.maximum(g2, 0)]
        flat_valid = pair_valid.reshape(-1)
        order = jnp.argsort(~flat_valid, stable=True)
        take = order[:caps.pair_cap]
        pg = gg.reshape(-1)[take]
        ph = jnp.maximum(g2.reshape(-1), 0)[take]
        pvalid = flat_valid[take]
        if take.shape[0] < caps.pair_cap:
            # pair_cap exceeds the G*K pair universe: pad the compacted
            # prefix back up to the cap (all padding invalid) so the block
            # reshape below keeps its static shape
            pad = caps.pair_cap - take.shape[0]
            pg = jnp.pad(pg, (0, pad))
            ph = jnp.pad(ph, (0, pad))
            pvalid = jnp.pad(pvalid, (0, pad))
        ovf_pairs = jnp.sum(flat_valid) > caps.pair_cap

        # compacted core set of EVERY grid, computed once: each core grid
        # takes part in ~k_cap merge pairs, so hoisting the compaction out
        # of the pair blocks removes the dominant per-pair gather cost
        def gather_core_set(g):
            w = jnp.arange(caps.m_cap, dtype=jnp.int32)
            pidx = dg.starts[g] + w
            pidx = jnp.where(w < dg.counts[g], pidx, 0)
            flag = core_sorted[pidx] & (w < dg.counts[g])
            tgt = jnp.cumsum(flag.astype(jnp.int32)) - 1
            out = jnp.zeros((caps.m_cap,), jnp.int32)
            out = out.at[jnp.where(flag, tgt, caps.m_cap - 1)].max(
                jnp.where(flag, pidx, 0))
            m = flag.sum()
            setv = jnp.arange(caps.m_cap) < m
            return jnp.where(setv, out, 0), setv

        core_set_idx, core_set_valid = jax.vmap(gather_core_set)(
            jnp.arange(G, dtype=jnp.int32))                  # [G, m_cap]

        def merge_block(args):
            a_g, b_g, pv = args
            av = core_set_valid[a_g] & pv[:, None]
            bv = core_set_valid[b_g] & pv[:, None]
            yes, iters = fast_merging_batch(
                spts[core_set_idx[a_g]], av, spts[core_set_idx[b_g]], bv,
                eps, max_iters=caps.merge_iters)
            return yes & pv, iters

        PB = caps.pair_block
        n_pb = caps.pair_cap // PB
        if caps.packed:
            # the valid pairs are argsort-compacted to a prefix above, so
            # only ceil(n_valid / PB) blocks carry work; blocks past the
            # prefix would compute all-False rows, which is exactly the
            # initial value of ``merged`` -- skipping them is bit-identical
            n_valid_pairs = jnp.minimum(
                jnp.sum(flat_valid.astype(jnp.int32)), caps.pair_cap)
            nblk_m = (n_valid_pairs + PB - 1) // PB

            def merge_body(state):
                b, acc = state
                s = b * PB
                yes, _ = merge_block((
                    jax.lax.dynamic_slice(pg, (s,), (PB,)),
                    jax.lax.dynamic_slice(ph, (s,), (PB,)),
                    jax.lax.dynamic_slice(pvalid, (s,), (PB,))))
                return b + 1, jax.lax.dynamic_update_slice(acc, yes, (s,))

            merged = jax.lax.while_loop(
                lambda s: s[0] < nblk_m, merge_body,
                (jnp.int32(0), jnp.zeros((caps.pair_cap,), bool)))[1]
        else:
            merged, _ = jax.lax.map(
                merge_block, (pg.reshape(n_pb, PB), ph.reshape(n_pb, PB),
                              pvalid.reshape(n_pb, PB)))
            merged = merged.reshape(-1)

    with jax.named_scope("grit.components"):
        edges = jnp.stack([pg, ph], axis=1)
        grid_label = label_propagation(G, edges, merged, core_grid)
        # representative grid index per cluster; sentinel G for non-core grids
        num_clusters = jnp.sum((grid_label == jnp.arange(G)) & core_grid)

    # ---- step 4: border / noise ----------------------------------------
    with jax.named_scope("grit.border"):
        def border_rows(gsel, width, active):
            cand_idx, cand_grid, cand_valid, _ = _candidates_for_grids(
                dg, nbr, gsel, width)
            cand_valid = cand_valid & core_sorted[cand_idx]
            own_slot = jnp.arange(p_cap, dtype=jnp.int32)[None, :]
            own_idx = dg.starts[gsel][:, None] + own_slot
            small = (~big[gsel]) & occupied[gsel] & active
            own_valid = (own_slot < dg.counts[gsel][:, None]) & small[:, None]
            own_idx_s = jnp.where(own_valid, own_idx, 0)
            noncore = own_valid & ~core_sorted[own_idx_s]
            a = spts[own_idx_s]
            b = spts[cand_idx]
            if caps.use_kernels:
                anchor = grid_anchor(gsel)
                dbest, jbest = kernel_ops.row_min_batch(a - anchor, b - anchor,
                                                        valid_b=cand_valid)
                # jbest == -1: no core candidate at all (row_min contract);
                # dbest is inf there, so the eps2 test already rejects it --
                # the clamp only keeps the gather in range
                gbest = jnp.take_along_axis(cand_grid,
                                            jnp.maximum(jbest, 0), axis=1)
            else:
                d2 = jnp.sum((a[:, :, None, :] - b[:, None, :, :]) ** 2,
                             axis=-1)
                d2 = jnp.where(cand_valid[:, None, :], d2, jnp.inf)
                jbest = jnp.argmin(d2, axis=2)
                dbest = jnp.take_along_axis(d2, jbest[..., None],
                                            axis=2)[..., 0]
                gbest = jnp.take_along_axis(cand_grid, jbest, axis=1)
            lab = jnp.where((dbest <= eps2) & noncore,
                            grid_label[gbest], jnp.int32(G))
            return own_idx_s, jnp.where(noncore, lab, G)

        if caps.packed:
            border_sorted = sweep_tiers(
                border_rows, jnp.full((n,), jnp.int32(G)),
                lambda acc, oi, v: acc.at[oi.reshape(-1)].min(v.reshape(-1)))
        else:
            b_own_idx, b_lab = jax.lax.map(
                lambda gsel: border_rows(gsel, caps.c_cap, ones), gsel_all)
            border_sorted = jnp.full((n,), jnp.int32(G)).at[
                b_own_idx.reshape(-1)].min(b_lab.reshape(-1))

    with jax.named_scope("grit.output"):
        lab_sorted = jnp.where(core_sorted, grid_label[dg.point_grid],
                               border_sorted)
        lab_sorted = jnp.where(lab_sorted >= G, -1, lab_sorted)
        lab_sorted = jnp.where(sorted_valid, lab_sorted, -1)

        labels = jnp.zeros((n,), jnp.int32).at[dg.order].set(lab_sorted)
        core = jnp.zeros((n,), bool).at[dg.order].set(core_sorted)
        point_grid = jnp.zeros((n,), jnp.int32).at[dg.order].set(dg.point_grid)
    report = OverflowReport(
        grid=dg.overflow, frontier=ovf_frontier, neighbors=ovf_k,
        candidates=ovf_candidates, core_set=ovf_core_set, pairs=ovf_pairs,
        halo=jnp.zeros((), bool))
    return DeviceDBSCANResult(labels=labels, core=core,
                              point_grid=point_grid,
                              num_clusters=num_clusters,
                              overflow=report.any(), report=report,
                              dispatch_tiers=dispatch_tiers)
