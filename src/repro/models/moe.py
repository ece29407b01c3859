"""Mixture-of-Experts FFN (mixtral 8e top-2, arctic 128e top-2 + dense).

TPU adaptation: sort-based (MegaBlocks-style) dispatch with a static
per-expert capacity rather than the [T, E, C] one-hot dispatch einsum
(which is O(T*E*C) memory -- infeasible at T=1M tokens, E=128).

  1. top-k routing (f32 softmax over router logits),
  2. flat (token, choice) list sorted by expert id; position-in-expert by
     rank arithmetic,
  3. gather tokens into a dense [E, C, d] buffer (capacity-dropped tokens
     fall into a zero row),
  4. batched expert GLU FFN: einsums with the E axis sharded over the
     'model'/'expert' mesh axis,
  5. weighted scatter-add back to token positions.

Load-balancing auxiliary loss follows the switch-transformer formulation.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np
import jax
import jax.numpy as jnp

from .config import LMConfig
from .layers import dense_init, split
from .sharding_ctx import constrain, get_shardmap_moe


def moe_params(cfg: LMConfig, key) -> dict:
    m = cfg.moe
    d = cfg.d_model
    pd = jnp.dtype(cfg.param_dtype)
    k_r, k_g, k_u, k_d = split(key, 4)
    p = {
        "router": dense_init(k_r, d, m.num_experts, pd, scale=0.02),
        "w_gate": jax.vmap(lambda k: dense_init(k, d, m.d_ff, pd))(
            jax.random.split(k_g, m.num_experts)),
        "w_up": jax.vmap(lambda k: dense_init(k, d, m.d_ff, pd))(
            jax.random.split(k_u, m.num_experts)),
        "w_down": jax.vmap(lambda k: dense_init(k, m.d_ff, d, pd))(
            jax.random.split(k_d, m.num_experts)),
    }
    return p


def capacity(cfg: LMConfig, num_tokens: int) -> int:
    m = cfg.moe
    c = int(m.capacity_factor * m.top_k * num_tokens / m.num_experts)
    return max(8, ((c + 7) // 8) * 8)


def moe_forward(cfg: LMConfig, p: dict, x: jnp.ndarray
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: [B, S, d] -> (y [B, S, d], aux_loss scalar f32)."""
    ctx = get_shardmap_moe()
    if ctx is not None:
        mesh, batch_axes, model_axis = ctx
        sizes = dict(mesh.shape)
        n_data = 1
        for a in batch_axes:
            n_data *= sizes[a]
        if n_data > 1 and cfg.moe.num_experts % n_data == 0 and \
                cfg.moe.d_ff % sizes[model_axis] == 0:
            return moe_forward_shardmap_ep(cfg, p, x, *ctx)
        return moe_forward_shardmap(cfg, p, x, *ctx)
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    E, K = m.num_experts, m.top_k
    C = capacity(cfg, T)
    xf = x.reshape(T, d)

    # ---- routing (f32) ----
    logits = (xf.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                    # [T, E]
    top_p, top_e = jax.lax.top_k(probs, K)                     # [T, K]
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # load-balancing aux loss (switch-style)
    me = probs.mean(axis=0)                                    # [E]
    ce = jnp.zeros((E,), jnp.float32).at[top_e.reshape(-1)].add(
        1.0 / (T * K))
    aux = m.router_aux_weight * E * jnp.sum(me * ce)

    # ---- sort-based dispatch ----
    flat_e = top_e.reshape(T * K)                              # expert of choice
    flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)     # token of choice
    flat_w = top_p.reshape(T * K).astype(x.dtype)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    counts = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)
    offsets = jnp.cumsum(counts) - counts                      # [E]
    pos_in_e = jnp.arange(T * K, dtype=jnp.int32) - offsets[se]
    keep = pos_in_e < C                                        # capacity drop
    slot = jnp.where(keep, se * C + pos_in_e, E * C)           # pad slot

    # gather tokens into expert buffers (+1 zero pad row)
    tok_for_slot = jnp.full((E * C + 1,), T, jnp.int32).at[slot].set(
        jnp.where(keep, st, T))[:E * C]
    w_for_slot = jnp.zeros((E * C + 1,), x.dtype).at[slot].set(
        jnp.where(keep, sw, 0))[:E * C]
    xpad = jnp.concatenate([xf, jnp.zeros((1, d), x.dtype)])
    expert_in = xpad[tok_for_slot].reshape(E, C, d)
    # steer GSPMD toward all-to-all dispatch (expert axis over 'model')
    # instead of all-gathering x across the model axis (§Perf lever; the
    # launcher enables the "moe_ecd" tag when experts are model-sharded)
    expert_in = constrain(expert_in, "moe_ecd")

    # ---- batched expert FFN (E axis shardable over 'model') ----
    # "moe_w_in"/"moe_w_out" re-lay the *compute* copy of the FSDP-stored
    # weights (Megatron column/row-parallel): a per-layer weight
    # all-gather over 'data' replaces the (much larger) activation
    # all-reduce GSPMD otherwise inserts for the d-contraction (§Perf).
    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    wg = constrain(p["w_gate"].astype(x.dtype), "moe_w_in")
    wu = constrain(p["w_up"].astype(x.dtype), "moe_w_in")
    wd = constrain(p["w_down"].astype(x.dtype), "moe_w_out")
    h = act(jnp.einsum("ecd,edf->ecf", expert_in, wg)) * \
        jnp.einsum("ecd,edf->ecf", expert_in, wu)
    expert_out = jnp.einsum("ecf,efd->ecd", h, wd)             # [E, C, d]
    expert_out = constrain(expert_out, "moe_ecd")

    # ---- weighted combine ----
    flat_out = expert_out.reshape(E * C, d) * w_for_slot[:, None]
    y = jnp.zeros((T + 1, d), x.dtype).at[tok_for_slot].add(flat_out)[:T]
    return y.reshape(B, S, d), aux


def moe_forward_shardmap_ep(cfg: LMConfig, p: dict, x: jnp.ndarray,
                            mesh, batch_axes, model_axis
                            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel MoE: experts sharded over 'data', FFN dim over
    'model' -- the GShard/DeepSpeed all-to-all pattern (§Perf, arctic).

    Storage == compute layout (see sharding.param_pspec with moe_ep), so
    there is NO per-layer weight gather.  Per layer the only collectives
    are two token all-to-alls over 'data' (top-k token copies, not full
    activations) and the ff-slice psum over 'model':

      1. each data shard buckets its tokens by destination shard
         (= owner row of the routed expert) into [n_data, E_loc, C, d];
      2. all-to-all over 'data' delivers [n_data(source), E_loc, C, d];
      3. local batched FFN on the chip's [E_loc, ff/n_model] slice;
      4. reverse all-to-all returns outputs to each token's home shard,
         which combines with its locally-kept slot->token map;
      5. psum over 'model' sums the ff slices.

    Requires E % n_data == 0 and ff % n_model == 0.
    """
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    B, S, d = x.shape
    E, K = m.num_experts, m.top_k
    sizes = dict(mesh.shape)
    n_model = sizes[model_axis]
    n_data = 1
    for a in batch_axes:
        n_data *= sizes[a]
    assert E % n_data == 0 and m.d_ff % n_model == 0
    E_loc = E // n_data
    assert B % n_data == 0
    B_loc = B // n_data
    T_loc = B_loc * S
    # capacity per (source shard, expert)
    C = max(8, int(np.ceil(m.capacity_factor * K * T_loc / E / 8)) * 8)
    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu

    def body(xb, router, wg, wu, wd):
        # xb [B_loc, S, d]; wg/wu [E_loc, d, ff_loc]; wd [E_loc, ff_loc, d]
        xf = xb.reshape(T_loc, d)
        logits = xf.astype(jnp.float32) @ router.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, K)
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
        me = probs.mean(axis=0)
        ce = jnp.zeros((E,), jnp.float32).at[top_e.reshape(-1)].add(
            1.0 / (T_loc * K))
        aux = m.router_aux_weight * E * jnp.sum(me * ce)
        aux = jax.lax.pmean(aux, batch_axes)

        # bucket my tokens into [n_data(dest), E_loc, C] slots
        flat_e = top_e.reshape(T_loc * K)              # global expert id
        flat_t = jnp.repeat(jnp.arange(T_loc, dtype=jnp.int32), K)
        flat_w = top_p.reshape(T_loc * K).astype(xb.dtype)
        order = jnp.argsort(flat_e, stable=True)
        se, st, sw = flat_e[order], flat_t[order], flat_w[order]
        counts = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)
        offsets = jnp.cumsum(counts) - counts
        pos = jnp.arange(T_loc * K, dtype=jnp.int32) - offsets[se]
        keep = pos < C
        slot = jnp.where(keep, se * C + pos, E * C)    # (dest,e_loc,c) flat
        tok = jnp.full((E * C + 1,), T_loc, jnp.int32).at[slot].set(
            jnp.where(keep, st, T_loc))[:E * C]
        w_slot = jnp.zeros((E * C + 1,), xb.dtype).at[slot].set(
            jnp.where(keep, sw, 0))[:E * C]
        xpad = jnp.concatenate([xf, jnp.zeros((1, d), xb.dtype)])
        send = xpad[tok].reshape(n_data, E_loc * C, d)

        # ---- all-to-all over the (possibly multi-name) data axes ----
        recv = jax.lax.all_to_all(send, batch_axes, split_axis=0,
                                  concat_axis=0)       # [n_data(src), ...]
        expert_in = recv.reshape(n_data, E_loc, C, d).transpose(1, 0, 2, 3) \
            .reshape(E_loc, n_data * C, d)
        h = act(jnp.einsum("ecd,edf->ecf", expert_in, wg)) * \
            jnp.einsum("ecd,edf->ecf", expert_in, wu)
        out = jnp.einsum("ecf,efd->ecd", h, wd)        # [E_loc, n_data*C, d]
        back = out.reshape(E_loc, n_data, C, d).transpose(1, 0, 2, 3) \
            .reshape(n_data, E_loc * C, d)
        ret = jax.lax.all_to_all(back, batch_axes, split_axis=0,
                                 concat_axis=0)        # my slots again
        flat_out = ret.reshape(E * C, d) * w_slot[:, None]
        y = jnp.zeros((T_loc + 1, d), xb.dtype).at[tok].add(flat_out)[:T_loc]
        y = jax.lax.psum(y, model_axis)                # sum ff slices
        return y.reshape(B_loc, S, d), aux

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(batch_axes, None, None), P(None, None),
                  P(batch_axes, None, model_axis),
                  P(batch_axes, None, model_axis),
                  P(batch_axes, model_axis, None)),
        out_specs=(P(batch_axes, None, None), P()),
        check_vma=False)
    return fn(x, p["router"],
              p["w_gate"].astype(x.dtype), p["w_up"].astype(x.dtype),
              p["w_down"].astype(x.dtype))


def moe_forward_shardmap(cfg: LMConfig, p: dict, x: jnp.ndarray,
                         mesh, batch_axes, model_axis
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Manual-SPMD MoE (the beyond-paper collective fix, §Perf).

    Key insight: under the (data, model) mesh, activations are already
    *replicated over the model axis* within each data shard, so every
    model shard can locally bucket the tokens destined for the experts
    it owns -- dispatch needs NO communication at all.  The only
    collective is one psum of the combined output over 'model' (the
    Megatron row-parallel reduction), replacing the activation
    all-reduces / replicating gathers GSPMD derives from the global-sort
    formulation in ``moe_forward``.

    Experts map onto the model axis as ``V = max(E, n_model)`` virtual
    experts: E >= n_model shards whole experts (arctic 128/16); E <
    n_model splits each expert's FFN dim into ``n_model/E`` column
    halves (mixtral 8 -> 16), whose partial down-projections the same
    psum recombines exactly.

    Capacity is per (shard, expert) -- drops differ slightly from the
    global-capacity reference; equivalence at high capacity_factor is
    tested.
    """
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    B, S, d = x.shape
    E, K = m.num_experts, m.top_k
    n_model = dict(mesh.shape)[model_axis]
    n_batch = 1
    for a in batch_axes:
        n_batch *= dict(mesh.shape)[a]
    assert B % n_batch == 0
    B_loc = B // n_batch
    T_loc = B_loc * S
    if E % n_model == 0:
        split, v_loc = 1, E // n_model
    else:
        assert n_model % E == 0, (E, n_model)
        split, v_loc = n_model // E, 1
    ff = m.d_ff
    assert ff % split == 0
    ff_v = ff // split
    C = max(8, int(np.ceil(m.capacity_factor * K * T_loc / E / 8)) * 8)

    # virtual-expert weight layout [V, d|ff_v, ...] built in GSPMD land;
    # the shard_map in_spec places V on 'model' (a per-layer weight gather
    # over 'data' where the stored layout was FSDP-sharded).
    def to_virtual(w, axis):           # axis: which dim holds ff
        if split == 1:
            return w
        if axis == 2:                  # [E, d, ff] -> [V, d, ff_v]
            return w.reshape(E, d, split, ff_v).transpose(0, 2, 1, 3) \
                .reshape(E * split, d, ff_v)
        # [E, ff, d] -> [V, ff_v, d]
        return w.reshape(E, split, ff_v, d).reshape(E * split, ff_v, d)

    wg = to_virtual(p["w_gate"].astype(x.dtype), 2)
    wu = to_virtual(p["w_up"].astype(x.dtype), 2)
    wd = to_virtual(p["w_down"].astype(x.dtype), 1)
    router = p["router"]
    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu

    def body(xb, router, wg, wu, wd):
        j = jax.lax.axis_index(model_axis)
        xf = xb.reshape(T_loc, d)
        logits = xf.astype(jnp.float32) @ router.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, K)
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
        me = probs.mean(axis=0)
        ce = jnp.zeros((E,), jnp.float32).at[top_e.reshape(-1)].add(
            1.0 / (T_loc * K))
        aux = m.router_aux_weight * E * jnp.sum(me * ce)
        aux = jax.lax.pmean(aux, batch_axes)

        flat_e = top_e.reshape(T_loc * K)
        flat_t = jnp.repeat(jnp.arange(T_loc, dtype=jnp.int32), K)
        flat_w = top_p.reshape(T_loc * K).astype(xb.dtype)
        if split == 1:
            e0 = j * v_loc
            local_e = flat_e - e0
            mine = (flat_e >= e0) & (flat_e < e0 + v_loc)
        else:
            local_e = jnp.zeros_like(flat_e)
            mine = flat_e == j // split
        key = jnp.where(mine, local_e, v_loc)
        order = jnp.argsort(key, stable=True)
        se, st, sw = key[order], flat_t[order], flat_w[order]
        counts = jnp.zeros((v_loc + 1,), jnp.int32).at[key].add(1)
        offsets = jnp.cumsum(counts) - counts
        pos = jnp.arange(T_loc * K, dtype=jnp.int32) - offsets[se]
        keep = (se < v_loc) & (pos < C)
        slot = jnp.where(keep, se * C + pos, v_loc * C)
        tok = jnp.full((v_loc * C + 1,), T_loc, jnp.int32).at[slot].set(
            jnp.where(keep, st, T_loc))[:v_loc * C]
        w_slot = jnp.zeros((v_loc * C + 1,), xb.dtype).at[slot].set(
            jnp.where(keep, sw, 0))[:v_loc * C]
        xpad = jnp.concatenate([xf, jnp.zeros((1, d), xb.dtype)])
        expert_in = xpad[tok].reshape(v_loc, C, d)
        h = act(jnp.einsum("ecd,edf->ecf", expert_in, wg)) * \
            jnp.einsum("ecd,edf->ecf", expert_in, wu)
        out = jnp.einsum("ecf,efd->ecd", h, wd)
        flat_out = out.reshape(v_loc * C, d) * w_slot[:, None]
        y = jnp.zeros((T_loc + 1, d), xb.dtype).at[tok].add(flat_out)[:T_loc]
        y = jax.lax.psum(y, model_axis)
        return y.reshape(B_loc, S, d), aux

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(batch_axes, None, None), P(None, None),
                  P(model_axis, None, None), P(model_axis, None, None),
                  P(model_axis, None, None)),
        out_specs=(P(batch_axes, None, None), P()),
        check_vma=False)
    return fn(x, router, wg, wu, wd)


def moe_forward_dense_fallback(cfg: LMConfig, p: dict, x: jnp.ndarray
                               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Oracle: computes every expert densely and mixes by router weights.

    O(T * E * ff) compute -- only for tests of the sparse dispatch path.
    """
    m = cfg.moe
    B, S, d = x.shape
    xf = x.reshape(-1, d)
    logits = xf.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, m.top_k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    w = jnp.zeros_like(probs).at[
        jnp.arange(xf.shape[0])[:, None], top_e].set(top_p)    # [T, E]
    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    h = act(jnp.einsum("td,edf->tef", xf, p["w_gate"].astype(x.dtype))) * \
        jnp.einsum("td,edf->tef", xf, p["w_up"].astype(x.dtype))
    out = jnp.einsum("tef,efd->ted", h, p["w_down"].astype(x.dtype))
    y = jnp.einsum("ted,te->td", out, w.astype(x.dtype))
    return y.reshape(B, S, d), jnp.zeros((), jnp.float32)
