"""Fine-grained Mixture-of-Experts with expert parallelism (shard_map).

Token->expert routing is the framework's showcase *irregular workload*
(DESIGN.md §2): expert loads are unbalanced exactly like UTS bags, and the
capacity mechanism (overflow drops) is the knob the paper's adaptive
controller reasons about.  Routing statistics (per-expert token counts)
are exported so ``core.characterization`` can compute their C_L.

Baseline dispatch = ``replicated``: tokens are replicated across the
"model" (expert) axis; every device routes all of its DP shard's tokens,
keeps the ones destined to its local experts, computes, and the outputs
are combined with a psum over the expert axis (the same collective shape
as a Megatron TP MLP).  This is correct for every (train/prefill/decode)
shape including seq=1.  The all-to-all dispatch path (tokens sharded over
the expert axis, 2x all_to_all instead of an all-reduce) is the §Perf
hillclimb variant — see ``dispatch="a2a"``.

DeepSeek conventions: softmax router -> top-k -> renormalize among the
picked experts; optional shared (always-on) experts run as a fused dense
MLP outside the dispatch.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from .config import MoEConfig
from .layers import dense, init_dense

__all__ = ["init_moe", "moe_block_local", "moe_apply", "shared_expert_mlp"]


def init_moe(key: jax.Array, d_model: int, cfg: MoEConfig,
             dtype=jnp.bfloat16) -> dict:
    ks = jax.random.split(key, 5)
    e, de = cfg.n_experts, cfg.d_expert
    scale = 1.0 / (d_model ** 0.5)

    def expert_stack(k, d_in, d_out):
        w = jax.random.normal(k, (e, d_in, d_out), jnp.float32)
        return (w / (d_in ** 0.5)).astype(dtype)

    p = {
        "router": {"w": (jax.random.normal(ks[0], (d_model, e), jnp.float32)
                         * scale)},  # router kept in f32
        "gate": expert_stack(ks[1], d_model, de),
        "up": expert_stack(ks[2], d_model, de),
        "down": expert_stack(ks[3], de, d_model),
    }
    if cfg.n_shared:
        p["shared"] = {
            "gate": init_dense(ks[4], d_model, cfg.n_shared * de, dtype),
            "up": init_dense(ks[4], d_model, cfg.n_shared * de, dtype),
            "down": init_dense(ks[4], cfg.n_shared * de, d_model, dtype),
        }
    return p


def shared_expert_mlp(params: dict, x: jax.Array) -> jax.Array:
    h = jax.nn.silu(dense(params["gate"], x)) * dense(params["up"], x)
    return dense(params["down"], h)


def _route(router_w: jax.Array, x_flat: jax.Array, cfg: MoEConfig
           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """-> (weights [T,k], experts [T,k] int32, aux_loss scalar)."""
    logits = x_flat.astype(jnp.float32) @ router_w          # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, cfg.top_k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss: E * sum_e f_e * p_e
    e = cfg.n_experts
    f = jnp.zeros((e,), jnp.float32).at[top_e.reshape(-1)].add(
        1.0 / (top_e.size))
    p_mean = probs.mean(axis=0)
    aux = e * jnp.sum(f * p_mean)
    return top_w, top_e, aux


def moe_block_local(params: dict, x_loc: jax.Array, cfg: MoEConfig, *,
                    n_shards: int, shard_ix: jax.Array,
                    tp_axis: Optional[str], act: str = "silu"
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Per-device MoE body (replicated dispatch, expert-sharded weights).

    x_loc:   [T, D] — this DP shard's tokens (replicated over tp_axis)
    params:  expert stacks already *local* ([E_loc, ...]); router full.
    returns  (partial output [T, D] — needs psum over tp_axis —,
              aux loss scalar, per-local-expert token counts [E_loc])
    """
    t, d = x_loc.shape
    e_loc = params["gate"].shape[0]
    top_w, top_e, aux = _route(params["router"]["w"], x_loc, cfg)

    # map global expert ids -> local slot (or drop if owned elsewhere)
    first = shard_ix * e_loc
    local_e = top_e - first                                   # [T, k]
    mine = (local_e >= 0) & (local_e < e_loc)
    # capacity per expert: mean load x capacity_factor (static shape)
    capacity = max(4, int(t * cfg.top_k * cfg.capacity_factor
                          / cfg.n_experts + 0.999))

    flat_e = jnp.where(mine, local_e, e_loc).reshape(-1)      # e_loc = drop
    flat_t = jnp.repeat(jnp.arange(t), cfg.top_k)

    # position of each (token, k) pair within its expert's capacity slots
    sort_ix = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[sort_ix]
    counts = jnp.zeros((e_loc + 1,), jnp.int32).at[flat_e].add(1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts)[:-1]])
    pos_sorted = jnp.arange(flat_e.size, dtype=jnp.int32) - starts[sorted_e]
    pos = jnp.zeros_like(pos_sorted).at[sort_ix].set(pos_sorted)

    # §Perf: GATHER-based dispatch.  Scatter-built buffers lowered to
    # read-modify-write with per-element u32 index traffic and f32
    # accumulator promotion; a pure gather of each capacity slot's
    # source row avoids all of it.  Slot (e, c) is filled by the c-th
    # (stable-sorted) pair routed to e — identical drop semantics.
    slot_src = starts[:e_loc, None] + jnp.arange(capacity)[None, :]
    valid = jnp.arange(capacity)[None, :] < counts[:e_loc, None]
    slot_pair = jnp.take(sort_ix, jnp.clip(slot_src, 0, flat_e.size - 1))
    slot_tok = jnp.where(valid, jnp.take(flat_t, slot_pair), t)
    x_pad = jnp.concatenate([x_loc, jnp.zeros((1, d), x_loc.dtype)])
    buf = jnp.take(x_pad, slot_tok, axis=0)            # [E_loc, C, D]

    # expert FFN (dense batched matmul on the MXU)
    h = jnp.einsum("ecd,edf->ecf", buf, params["gate"])
    h2 = jnp.einsum("ecd,edf->ecf", buf, params["up"])
    h = (jax.nn.silu(h) if act == "silu" else jax.nn.gelu(h)) * h2
    y_buf = jnp.einsum("ecf,efd->ecd", h, params["down"])

    # combine: gather each pair's slot, weight in the activation dtype,
    # and reduce over k by reshape (pairs are (t, k)-contiguous) — no
    # scatter-add.
    in_cap = (pos < capacity) & (flat_e < e_loc)
    flat_w = jnp.where(mine.reshape(-1) & in_cap, top_w.reshape(-1), 0.0)
    flat_ix = jnp.where(in_cap, flat_e * capacity + pos, e_loc * capacity)
    y_pad = jnp.concatenate(
        [y_buf.reshape(e_loc * capacity, d),
         jnp.zeros((1, d), y_buf.dtype)])
    gathered = jnp.take(y_pad, flat_ix, axis=0)        # [T*k, D]
    gathered = gathered * flat_w[:, None].astype(y_buf.dtype)
    out = gathered.reshape(t, cfg.top_k, d).sum(axis=1)

    counts_loc = counts[:e_loc]
    return out, aux, counts_loc


def _moe_a2a_local(params: dict, x_loc: jax.Array, cfg: MoEConfig, *,
                   n_shards: int, tp_axis: str, act: str
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """All-to-all expert-parallel MoE body (§Perf hillclimb variant).

    x_loc: [T_loc, D] — this device's *sequence shard* of tokens (the
    residual stream stays seq-sharded; no token replication).  Each
    (token, k) pair is bucketed to the shard owning its expert, shipped
    with a fixed per-peer capacity all_to_all, computed locally with the
    gather dispatch, and shipped back.  Link bytes per device ~
    2 * T_loc * k * cf * D — ~3x less than the replicated-dispatch psum,
    with dispatch compute and buffers 1/n_shards of the replicated path.
    """
    t, d = x_loc.shape
    e_loc = params["gate"].shape[0]
    top_w, top_e, aux = _route(params["router"]["w"], x_loc, cfg)

    k = cfg.top_k
    npairs = t * k
    flat_e = top_e.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(t), k)
    dest = flat_e // e_loc                                 # owner shard
    le = flat_e % e_loc                                    # local expert

    # per-destination send capacity (uniform-load x cf, like experts)
    c_send = max(4, int(npairs * cfg.capacity_factor / n_shards + 0.999))

    # rank of each pair within its destination bucket (stable)
    sort_ix = jnp.argsort(dest, stable=True)
    sorted_d = dest[sort_ix]
    dcounts = jnp.zeros((n_shards + 1,), jnp.int32).at[dest].add(1)
    dstarts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(dcounts)[:-1]])
    rank_sorted = jnp.arange(npairs, dtype=jnp.int32) - dstarts[sorted_d]
    rank = jnp.zeros_like(rank_sorted).at[sort_ix].set(rank_sorted)
    in_send = rank < c_send

    # gather-built send buckets [n_shards, C_send, *]
    slot_src = dstarts[:n_shards, None] + jnp.arange(c_send)[None, :]
    valid = jnp.arange(c_send)[None, :] < dcounts[:n_shards, None]
    slot_pair = jnp.take(sort_ix, jnp.clip(slot_src, 0, npairs - 1))
    slot_tok = jnp.where(valid, jnp.take(flat_t, slot_pair), t)
    x_pad = jnp.concatenate([x_loc, jnp.zeros((1, d), x_loc.dtype)])
    send_x = jnp.take(x_pad, slot_tok, axis=0)         # [P, C_send, D]
    send_le = jnp.where(valid, jnp.take(le, slot_pair),
                        e_loc).astype(jnp.int32)       # [P, C_send]

    recv_x = jax.lax.all_to_all(send_x, tp_axis, 0, 0, tiled=False)
    recv_le = jax.lax.all_to_all(send_le, tp_axis, 0, 0, tiled=False)
    rx = recv_x.reshape(n_shards * c_send, d)
    rle = recv_le.reshape(n_shards * c_send)

    # local dispatch by expert (gather form, k=1)
    tr = rx.shape[0]
    c_loc = max(4, int(tr * cfg.capacity_factor / e_loc + 0.999))
    sort2 = jnp.argsort(rle, stable=True)
    sorted_e2 = rle[sort2]
    ecounts = jnp.zeros((e_loc + 1,), jnp.int32).at[rle].add(1)
    estarts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(ecounts)[:-1]])
    pos2_sorted = jnp.arange(tr, dtype=jnp.int32) - estarts[sorted_e2]
    pos2 = jnp.zeros_like(pos2_sorted).at[sort2].set(pos2_sorted)

    eslot_src = estarts[:e_loc, None] + jnp.arange(c_loc)[None, :]
    evalid = jnp.arange(c_loc)[None, :] < ecounts[:e_loc, None]
    eslot_row = jnp.where(evalid,
                          jnp.take(sort2, jnp.clip(eslot_src, 0, tr - 1)),
                          tr)
    rx_pad = jnp.concatenate([rx, jnp.zeros((1, d), rx.dtype)])
    buf = jnp.take(rx_pad, eslot_row, axis=0)          # [E_loc, C_loc, D]

    h = jnp.einsum("ecd,edf->ecf", buf, params["gate"])
    h2 = jnp.einsum("ecd,edf->ecf", buf, params["up"])
    h = (jax.nn.silu(h) if act == "silu" else jax.nn.gelu(h)) * h2
    y_buf = jnp.einsum("ecf,efd->ecd", h, params["down"])

    # back to recv-row order, then reverse all_to_all
    row_ok = (rle < e_loc) & (pos2 < c_loc)
    row_ix = jnp.where(row_ok, rle * c_loc + pos2, e_loc * c_loc)
    y_pad = jnp.concatenate([y_buf.reshape(e_loc * c_loc, d),
                             jnp.zeros((1, d), y_buf.dtype)])
    y_rows = jnp.take(y_pad, row_ix, axis=0).reshape(n_shards, c_send, d)
    back = jax.lax.all_to_all(y_rows, tp_axis, 0, 0, tiled=False)
    back = back.reshape(n_shards * c_send, d)          # [P*C_send, D]

    # combine at the source: pair -> (dest, rank) bucket slot
    pair_ok = in_send
    pair_ix = jnp.where(pair_ok, dest * c_send + rank,
                        n_shards * c_send)
    back_pad = jnp.concatenate([back, jnp.zeros((1, d), back.dtype)])
    gathered = jnp.take(back_pad, pair_ix, axis=0)     # [T*k, D]
    w_ok = pair_ok
    flat_w = jnp.where(w_ok, top_w.reshape(-1), 0.0)
    gathered = gathered * flat_w[:, None].astype(back.dtype)
    out = gathered.reshape(t, k, d).sum(axis=1)

    counts_loc = ecounts[:e_loc]
    return out, aux, counts_loc


def moe_apply(params: dict, x: jax.Array, cfg: MoEConfig, *, mesh,
              dp_axes: Tuple[str, ...], tp_axis: str, act: str = "silu",
              dispatch: str = "replicated"
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """MoE over [B, S, D] activations under a (pod?, data, model) mesh.

    Returns (y [B,S,D], aux scalar, expert_counts [E]).
    """
    b, s, d = x.shape
    n_shards = mesh.shape[tp_axis]

    if dispatch == "a2a" and s % n_shards == 0 and s > 1:
        dp_size = 1
        for a in dp_axes:
            dp_size *= mesh.shape[a]
        dp_ok = b % dp_size == 0
        bspec = dp_axes if dp_ok else None

        def body_a2a(router_w, gate, up, down, x_blk):
            t_loc = x_blk.shape[0] * x_blk.shape[1]
            out, aux, counts = _moe_a2a_local(
                {"router": {"w": router_w}, "gate": gate, "up": up,
                 "down": down},
                x_blk.reshape(t_loc, d), cfg,
                n_shards=n_shards, tp_axis=tp_axis, act=act)
            aux = jax.lax.pmean(aux, tp_axis)
            if dp_ok:
                aux = jax.lax.pmean(aux, dp_axes)
                counts = jax.lax.psum(counts, dp_axes)
            return out.reshape(x_blk.shape), aux, counts

        y, aux, counts_loc = shard_map(
            body_a2a, mesh=mesh,
            in_specs=(P(), P(tp_axis, None, None),
                      P(tp_axis, None, None), P(tp_axis, None, None),
                      P(bspec, tp_axis, None)),
            out_specs=(P(bspec, tp_axis, None), P(), P(tp_axis)),
            check_vma=False,
        )(params["router"]["w"], params["gate"], params["up"],
          params["down"], x)
        if cfg.n_shared:
            y = y + shared_expert_mlp(params["shared"], x)
        return y, aux, counts_loc

    if dispatch not in ("replicated", "a2a"):
        raise ValueError(f"unknown dispatch {dispatch!r}")

    # batch not divisible by DP (e.g. long_500k's B=1): tokens replicate
    # over the dp axes and the combine skips the dp reduction.
    dp_size = 1
    for a in dp_axes:
        dp_size *= mesh.shape[a]
    dp_ok = b % dp_size == 0
    x_spec = P(dp_axes, None, None) if dp_ok else P(None, None, None)

    def body(router_w, gate, up, down, x_blk):
        shard_ix = jax.lax.axis_index(tp_axis)
        t_loc = x_blk.shape[0] * x_blk.shape[1]
        out, aux, counts = moe_block_local(
            {"router": {"w": router_w}, "gate": gate, "up": up,
             "down": down},
            x_blk.reshape(t_loc, d), cfg,
            n_shards=n_shards, shard_ix=shard_ix, tp_axis=tp_axis, act=act)
        out = jax.lax.psum(out, tp_axis)
        aux = jax.lax.pmean(aux, tp_axis)
        if dp_ok:
            aux = jax.lax.pmean(aux, dp_axes)
            counts = jax.lax.psum(counts, dp_axes)  # [E_loc] over DP
        return out.reshape(x_blk.shape), aux, counts

    y, aux, counts_loc = shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(tp_axis, None, None), P(tp_axis, None, None),
                  P(tp_axis, None, None), x_spec),
        out_specs=(x_spec, P(), P(tp_axis)),
        check_vma=False,
    )(params["router"]["w"], params["gate"], params["up"],
      params["down"], x)

    if cfg.n_shared:
        y = y + shared_expert_mlp(params["shared"], x)
    return y, aux, counts_loc
