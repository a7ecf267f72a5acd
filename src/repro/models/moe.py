"""Sparse MoE layer: top-k router, expert FFNs, expert-parallel execution.

Two execution paths share the same parameters:

* ``moe_apply_local``   — exact dense-combine reference: every expert runs on
                           every token, outputs combined by the routing mask.
                           Used on one device (smoke tests, the CPU engine)
                           and as the oracle for the sharded/capacity path.
* ``moe_apply_sharded`` — expert-parallel ``shard_map``: tokens replicated
                           across the model axis, each rank dispatches to its
                           local experts with a capacity buffer (scatter),
                           runs the grouped expert GEMM, combines, and
                           ``psum``s over the model axis.

The capacity-based dispatch mirrors the paper's planner assumption of evenly
distributed tokens per expert (MoE-Gen §4.2 "Sequential execution of
experts"); the capacity factor bounds worst-case memory exactly like the
paper bounds ``b_e`` to prevent OOM.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import dense_init
from repro.sharding.specs import ShardCtx


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------
def init_moe_params(cfg: ModelConfig, key) -> Dict[str, jax.Array]:
    d, e, f = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    p = {
        "router": dense_init(ks[0], (d, e), dtype=jnp.float32),
        "experts_w_gate": dense_init(ks[1], (e, d, f), in_dim=d, dtype=dt),
        "experts_w_up": dense_init(ks[2], (e, d, f), in_dim=d, dtype=dt),
        "experts_w_down": dense_init(ks[3], (e, f, d), in_dim=f, dtype=dt),
    }
    if cfg.num_shared_experts:
        fs = cfg.num_shared_experts * f
        kk = jax.random.split(jax.random.fold_in(key, 1), 3)
        p["shared"] = {
            "w_gate": dense_init(kk[0], (d, fs), dtype=dt),
            "w_up": dense_init(kk[1], (d, fs), dtype=dt),
            "w_down": dense_init(kk[2], (fs, d), dtype=dt),
        }
    return p


def route(cfg: ModelConfig, router_w: jax.Array, x: jax.Array):
    """Top-k routing.  x: (..., D).  Returns (gates, idx, probs).  The
    top-k gates are renormalised to sum to one unless
    ``cfg.norm_topk_prob`` is false, in which case they keep their softmax
    values."""
    logits = x.astype(jnp.float32) @ router_w               # (..., E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.experts_per_token)
    if cfg.norm_topk_prob:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, idx, probs


def shared_experts(p: Dict, x: jax.Array) -> jax.Array:
    """The always-on shared experts of a MoE layer (``p["shared"]``): one
    SwiGLU of ``num_shared_experts * moe_d_ff`` over every row, computed
    outside the routed dispatch."""
    w = p["shared"]
    return (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]


def load_balance_loss(cfg: ModelConfig, probs: jax.Array, idx: jax.Array):
    """Switch-style auxiliary load-balancing loss."""
    e = cfg.num_experts
    me = jnp.mean(probs.reshape(-1, e), axis=0)
    counts = jnp.zeros((e,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    frac = counts / jnp.maximum(counts.sum(), 1.0)
    return e * jnp.sum(me * frac)


def expert_ffn(wg, wu, wd, h):
    """Grouped expert FFN.  h: (E, C, D) -> (E, C, D)."""
    g = jnp.einsum("ecd,edf->ecf", h, wg)
    u = jnp.einsum("ecd,edf->ecf", h, wu)
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, wd)


# ---------------------------------------------------------------------------
# Grouped dispatch: capacity-bucketed gather -> one launch -> scatter-add
# ---------------------------------------------------------------------------
def _arrival_slots(ids: jax.Array, n_buckets: int, mask=None) -> jax.Array:
    """Slot of each routed copy within its bucket, in arrival order — the
    cumsum-of-one-hot core shared by every capacity-dispatch path (grouped,
    sharded psum, all-to-all).  Entries with ``mask=False`` consume no slot."""
    onehot = jax.nn.one_hot(ids, n_buckets, dtype=jnp.int32)
    if mask is not None:
        onehot = onehot * mask[:, None].astype(jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot
    return jnp.take_along_axis(pos, ids[:, None], axis=1)[:, 0]


def grouped_dispatch(
    cfg: ModelConfig,
    xt: jax.Array,          # (T, D) tokens
    gates: jax.Array,       # (T, k)
    idx: jax.Array,         # (T, k) expert ids
    wg, wu, wd,             # (E, ·, ·) expert weights
    capacity: int,
    use_kernel=None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """The engine's expert module (paper §4.2), fully on device.

    Routed token copies are gathered into an ``(E, C, D)`` capacity buffer,
    pushed through ONE grouped FFN launch (``kernels.ops.grouped_expert_ffn``:
    Pallas on TPU, XLA einsum elsewhere), and scatter-added back weighted by
    their gates.  ``capacity`` is the per-expert token budget ``b_e``; routed
    copies beyond it are dropped (zero contribution), which the caller
    accounts for.  Returns ``(y, kept, dropped, load)`` — ``kept``/
    ``dropped`` device scalars plus ``load``, the (E,) per-expert routed-copy
    histogram counted BEFORE capacity drops (what the planner's measured
    ``b_e`` search consumes) — no host sync happens here.
    """
    from repro.kernels import ops as kernel_ops

    T, D = xt.shape
    E = cfg.num_experts
    k = cfg.experts_per_token
    flat_idx = idx.reshape(-1)                              # (T*k,)
    flat_gate = gates.reshape(-1)
    slot = _arrival_slots(flat_idx, E)
    keep = slot < capacity
    slot_c = jnp.minimum(slot, capacity - 1)
    tok = jnp.arange(T * k) // k
    buf = jnp.zeros((E, capacity, D), xt.dtype)
    buf = buf.at[flat_idx, slot_c].add(
        xt[tok] * keep[:, None].astype(xt.dtype)
    )
    out = kernel_ops.grouped_expert_ffn(buf, wg, wu, wd, use_kernel=use_kernel)
    back = out[flat_idx, slot_c]                            # (T*k, D)
    back = back * (keep[:, None] * flat_gate[:, None]).astype(back.dtype)
    y = jnp.zeros((T, D), xt.dtype).at[tok].add(back.astype(xt.dtype))
    kept = jnp.sum(keep.astype(jnp.int32))
    load = jnp.zeros((E,), jnp.int32).at[flat_idx].add(1)
    return y, kept, jnp.int32(T * k) - kept, load


def ragged_tile(T: int) -> int:
    """Row tile of the ragged decode dispatch for ``T`` rows: 128, or ``T``
    rounded up to 16 when smaller.  At ``T <= 128`` every expert's copies
    fit one tile, since a row routes to an expert at most once."""
    return min(128, -(-T // 16) * 16)


def ragged_buffer_rows(T: int, k: int, E: int, capacity: int) -> int:
    """Static row count of the ragged buffer: each expert's kept copies
    rounded up to whole tiles, at most ``T*k + E*(tm-1)`` rows and at most
    ``E`` full capacity buffers of ``min(capacity, T)`` rows."""
    tm = ragged_tile(T)
    up = lambda n: -(-n // tm) * tm
    return up(min(T * k + E * (tm - 1), E * up(min(capacity, T))))


def _ragged_tiles(load: jax.Array, capacity: int, tm: int) -> jax.Array:
    """(E,) row tiles each expert takes: its kept copies over ``tm``."""
    return -(-jnp.minimum(load, capacity) // tm)


def ragged_rows(load: jax.Array, capacity: int, T: int) -> jax.Array:
    """Rows the ragged decode kernel computes for one call whose (E,)
    routed-copy histogram is ``load``: used tiles x the row tile."""
    tm = ragged_tile(T)
    return jnp.sum(_ragged_tiles(load, capacity, tm)) * tm


def ragged_dispatch(
    cfg: ModelConfig,
    xt: jax.Array,          # (T, D) tokens
    gates: jax.Array,       # (T, k)
    idx: jax.Array,         # (T, k) expert ids
    wg, wu, wd,             # (E, ·, ·) expert weights
    capacity: int,
    use_kernel=None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """``grouped_dispatch`` with the routed copies packed by expert into
    row tiles instead of an ``(E, capacity, D)`` buffer: decode's expert
    stage.  Same arguments, same drops (a copy whose arrival slot is at or
    past ``capacity``), same returns.

    Expert ``e``'s kept copies take ``ceil(kept_e / tm)`` tiles of
    ``tm = ragged_tile(T)`` rows at an exclusive-cumsum offset; the
    ragged kernel (``kernels.ops.ragged_expert_ffn``) computes only the
    used tiles and reads each expert's weights once per tile, so once a
    call wherever its copies fit a tile.  The tile -> expert map and the
    used count stay on device.  Where no TPU compiles the kernel, the
    capacity einsum of ``grouped_dispatch`` runs instead: same values."""
    from repro.kernels import ops as kernel_ops

    use_kernel = (kernel_ops.default_use_kernel() if use_kernel is None
                  else use_kernel)
    if not use_kernel:
        return grouped_dispatch(cfg, xt, gates, idx, wg, wu, wd, capacity,
                                use_kernel=False)
    T, D = xt.shape
    E = cfg.num_experts
    k = cfg.experts_per_token
    tm = ragged_tile(T)
    R = ragged_buffer_rows(T, k, E, capacity)
    flat_idx = idx.reshape(-1)                              # (T*k,)
    flat_gate = gates.reshape(-1)
    slot = _arrival_slots(flat_idx, E)
    keep = slot < capacity
    load = jnp.zeros((E,), jnp.int32).at[flat_idx].add(1)
    tiles = _ragged_tiles(load, capacity, tm)
    ends = jnp.cumsum(tiles)                                # (E,)
    row = (ends - tiles)[flat_idx] * tm + slot
    tok = jnp.arange(T * k) // k
    buf = jnp.zeros((R, D), xt.dtype).at[jnp.where(keep, row, R)].set(
        xt[tok], mode="drop")
    # tile t belongs to the first expert whose tiles end past it
    tile_expert = jnp.minimum(
        jnp.sum(jnp.arange(R // tm)[:, None] >= ends[None, :], axis=1),
        E - 1).astype(jnp.int32)
    out = kernel_ops.ragged_expert_ffn(buf, tile_expert, ends[-1], wg, wu,
                                       wd, block_m=tm)
    # dropped copies read no row: rows past the used tiles are unwritten
    back = out[jnp.where(keep, row, 0)]                     # (T*k, D)
    back = jnp.where(keep[:, None],
                     back * flat_gate[:, None].astype(back.dtype), 0)
    y = jnp.zeros((T, D), xt.dtype).at[tok].add(back.astype(xt.dtype))
    kept = jnp.sum(keep.astype(jnp.int32))
    return y, kept, jnp.int32(T * k) - kept, load


def moe_apply_grouped(
    cfg: ModelConfig,
    p: Dict[str, jax.Array],
    x: jax.Array,
    capacity: Optional[int] = None,
    use_kernel=None,
) -> Tuple[jax.Array, jax.Array]:
    """Grouped-dispatch MoE forward — the same implementation the engine's
    decode step uses, reachable from the reference forward via
    ``ShardCtx(moe_dispatch='grouped')`` so prefill and decode share one
    expert path.  ``capacity`` defaults to the planner-style
    ``moe_capacity`` bound (``capacity_factor`` headroom over the balanced
    load), so routed copies beyond it are dropped under imbalance; the
    kept/dropped counters are NOT surfaced here — callers needing drop
    accounting (the engine's decode stage) call ``grouped_dispatch``
    directly.  See ROADMAP "Grouped prefill by default"."""
    B, S, D = x.shape
    xt = x.reshape(-1, D)
    gates, idx, probs = route(cfg, p["router"], xt)
    cap = capacity if capacity is not None else moe_capacity(cfg, xt.shape[0])
    y, _, _, _ = grouped_dispatch(
        cfg, xt, gates, idx,
        p["experts_w_gate"], p["experts_w_up"], p["experts_w_down"],
        cap, use_kernel=use_kernel,
    )
    return y.reshape(B, S, D).astype(x.dtype), load_balance_loss(cfg, probs, idx)


def predict_experts(
    cfg: ModelConfig, next_router_w: jax.Array, x: jax.Array, khat: int
) -> jax.Array:
    """Predict the NEXT MoE layer's active expert set from the current
    hidden state (device-computed; (khat,) int32 ids).

    Layer *l*'s post-mixer state pushed through layer *l+1*'s router is a
    strong proxy for *l+1*'s actual routing (PAPERS.md: predictive
    prefetching) because the residual stream changes slowly between
    adjacent layers.  Batch-aggregated: softmax probabilities are summed
    over tokens and the top-k-hat experts by expected load are returned —
    the set worth moving bytes for.  Predictions steer PREFETCH only; the
    actual routing at *l+1* fetches any mispredicted expert on demand."""
    logits = x.astype(jnp.float32) @ next_router_w          # (..., E)
    probs = jax.nn.softmax(logits, axis=-1)
    scores = probs.reshape(-1, cfg.num_experts).sum(axis=0)
    _, ids = jax.lax.top_k(scores, min(khat, cfg.num_experts))
    return ids.astype(jnp.int32)


# ---------------------------------------------------------------------------
# Exact local reference
# ---------------------------------------------------------------------------
def moe_apply_local(
    cfg: ModelConfig, p: Dict[str, jax.Array], x: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Dense-combine MoE: exact, O(E * T * D * F) compute.  x: (B, S, D)."""
    B, S, D = x.shape
    xt = x.reshape(-1, D)
    gates, idx, probs = route(cfg, p["router"], xt)
    h = jnp.broadcast_to(xt[None], (cfg.num_experts,) + xt.shape)
    y_all = expert_ffn(
        p["experts_w_gate"], p["experts_w_up"], p["experts_w_down"], h
    )                                                       # (E, T, D)
    onehot = jax.nn.one_hot(idx, cfg.num_experts, dtype=jnp.float32)
    weight = jnp.einsum("tk,tke->te", gates, onehot)        # (T, E)
    y = jnp.einsum("te,etd->td", weight.astype(y_all.dtype), y_all)
    aux = load_balance_loss(cfg, probs, idx)
    return y.reshape(B, S, D).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# Capacity-buffer dispatch (single rank's share of experts)
# ---------------------------------------------------------------------------
def _dispatch_combine(
    cfg: ModelConfig,
    xt: jax.Array,          # (T, D) local tokens
    gates: jax.Array,       # (T, k)
    idx: jax.Array,         # (T, k) global expert ids
    wg, wu, wd,             # (E_loc, ·, ·) this rank's experts
    e_lo: jax.Array,        # scalar: first global expert id on this rank
    capacity: int,
):
    T, D = xt.shape
    k = cfg.experts_per_token
    e_loc_n = wg.shape[0]
    flat_idx = idx.reshape(-1)                              # (T*k,)
    flat_gate = gates.reshape(-1)
    local_e = flat_idx - e_lo
    mine = (local_e >= 0) & (local_e < e_loc_n)
    local_e_c = jnp.clip(local_e, 0, e_loc_n - 1)
    slot = _arrival_slots(local_e_c, e_loc_n, mask=mine)
    keep = mine & (slot < capacity)
    slot_c = jnp.minimum(slot, capacity - 1)
    tok = jnp.arange(T * k) // k
    buf = jnp.zeros((e_loc_n, capacity, D), xt.dtype)
    contrib = xt[tok] * keep[:, None].astype(xt.dtype)
    buf = buf.at[local_e_c, slot_c].add(contrib)
    from repro.kernels import ops as kernel_ops

    out_buf = kernel_ops.grouped_expert_ffn(buf, wg, wu, wd)  # (E_loc, C, D)
    back = out_buf[local_e_c, slot_c]                       # (T*k, D)
    back = back * (keep[:, None] * flat_gate[:, None]).astype(back.dtype)
    y = jnp.zeros((T, D), xt.dtype).at[tok].add(back.astype(xt.dtype))
    return y


def moe_capacity(cfg: ModelConfig, T: int) -> int:
    per = T * cfg.experts_per_token / max(cfg.num_experts, 1)
    c = int(per * cfg.capacity_factor) + 1
    return max(8, -(-c // 8) * 8)                           # round up to 8


def moe_apply_capacity_local(cfg, p, x):
    """Capacity-dispatch path on one device (oracle parity with sharded)."""
    B, S, D = x.shape
    xt = x.reshape(-1, D)
    gates, idx, probs = route(cfg, p["router"], xt)
    y = _dispatch_combine(
        cfg, xt, gates, idx,
        p["experts_w_gate"], p["experts_w_up"], p["experts_w_down"],
        jnp.int32(0), moe_capacity(cfg, xt.shape[0]),
    )
    return y.reshape(B, S, D), load_balance_loss(cfg, probs, idx)


# ---------------------------------------------------------------------------
# Expert-parallel shard_map path
# ---------------------------------------------------------------------------
def moe_apply_sharded(
    cfg: ModelConfig, p: Dict[str, jax.Array], x: jax.Array, ctx: ShardCtx,
    small_batch_threshold: int = 4096,
) -> Tuple[jax.Array, jax.Array]:
    """Expert parallelism over the model axis.

    Tokens stay replicated across the model axis (sharded over batch axes);
    each model rank builds the capacity buffer for its experts, runs the
    grouped GEMM, and the partial outputs are summed with a psum — the
    collective pattern of tensor-parallel MoE.  If the expert count does not
    divide the model axis, experts are replicated and ranks split tokens
    instead (tensor-parallel experts are handled upstream by the sharding
    rules on the weight matrices + the local path).
    """
    if ctx.mesh is None or ctx.model_axis is None:
        return moe_apply_local(cfg, p, x)
    n_model = ctx.model_size
    E = cfg.num_experts
    if E % n_model != 0 and n_model % E != 0:
        # irregular ratio: tensor-parallel experts via XLA on the sharded
        # weight F dim (sharding rules place 'model' there in this case).
        return moe_apply_local(cfg, p, x)
    B, S, _ = x.shape
    if B * S * cfg.experts_per_token <= small_batch_threshold:
        # decode-scale batches: the dense einsum over the *stored* weight
        # sharding moves ZERO weight bytes (partial sums over the sharded
        # dims reduce activation-sized tensors instead) — the paper's
        # Table-9 small-batch regime.  At this T the all-expert compute is
        # negligible, while both shard_map paths would move weights
        # (91 GB/step on jamba-398B decode, measured in the dry-run).
        return moe_apply_local(cfg, p, x)

    B, S, D = x.shape
    batch_spec = ctx.spec("batch", None, None, shape=x.shape)
    model = ctx.model_axis
    # E >= n_model: each rank owns E/n_model experts.
    # E <  n_model: each expert is replicated n_model/E times and the
    # replicas split the token stream (capacity divides accordingly).
    n_rep = max(1, n_model // E)
    expert_spec = P(model, None, None) if n_rep == 1 else P(None, None, None)

    def body(xl, router_w, wg, wu, wd):
        Bl, Sl, _ = xl.shape
        xt = xl.reshape(-1, D)
        gates, idx, probs = route(cfg, router_w, xt)
        rank = jax.lax.axis_index(model)
        cap = moe_capacity(cfg, xt.shape[0])
        if n_rep == 1:
            e_loc_n = wg.shape[0]
            e_lo = rank * e_loc_n
            y = _dispatch_combine(cfg, xt, gates, idx, wg, wu, wd, e_lo, cap)
        else:
            my_expert = rank % E
            replica = rank // E
            # keep only my replica's token share for my expert
            tok = jnp.arange(xt.shape[0] * cfg.experts_per_token) \
                // cfg.experts_per_token
            share = (tok % n_rep) == replica
            gates_m = jnp.where(
                share.reshape(gates.shape), gates, 0.0
            )
            idx_m = jnp.where(
                share.reshape(idx.shape), idx, -1
            )
            cap = max(8, -(-cap // n_rep))
            pick = lambda w: jax.lax.dynamic_index_in_dim(
                w, my_expert, 0, keepdims=True
            )
            y = _dispatch_combine(
                cfg, xt, gates_m, idx_m,
                pick(wg), pick(wu), pick(wd),
                my_expert, cap,
            )
        y = jax.lax.psum(y, model)
        aux = load_balance_loss(cfg, probs, idx)
        if ctx.batch_axes:
            aux = jax.lax.pmean(aux, ctx.batch_axes)
        return y.reshape(Bl, Sl, D), aux

    y, aux = jax.shard_map(
        body,
        mesh=ctx.mesh,
        in_specs=(
            batch_spec,
            P(),                       # router replicated
            expert_spec,
            expert_spec,
            expert_spec,
        ),
        out_specs=(batch_spec, P()),
        check_vma=False,
    )(x, p["router"], p["experts_w_gate"], p["experts_w_up"], p["experts_w_down"])
    return y.astype(x.dtype), aux


# ---------------------------------------------------------------------------
# All-to-all dispatch (beyond-paper: tokens sharded over the model axis too)
# ---------------------------------------------------------------------------
def moe_apply_a2a(
    cfg: ModelConfig, p: Dict[str, jax.Array], x: jax.Array, ctx: ShardCtx
) -> Tuple[jax.Array, jax.Array]:
    """Expert parallelism with all-to-all token exchange.

    Unlike ``moe_apply_sharded`` (tokens replicated over the model axis,
    combined with a psum of the full activation), tokens here are sharded
    over the model axis as well: each rank routes only its own T/n tokens
    and ships each routed copy once to the rank owning its expert —
    k*T*D/n bytes each way instead of the psum's 2*T*D, and 1/n of the
    routing + dispatch work.  Requires E % n_model == 0 and the flattened
    token count divisible by n_model.
    """
    n_model = ctx.model_size
    E = cfg.num_experts
    B, S, D = x.shape
    T = B * S
    if (
        ctx.mesh is None or ctx.model_axis is None or n_model == 1
        or E % n_model != 0 or T % n_model != 0
    ):
        return moe_apply_sharded(cfg, p, x, ctx)

    model = ctx.model_axis
    batch_spec = ctx.spec("batch", None, None, shape=x.shape)
    e_loc_n = E // n_model

    def body(xl, router_w, wg, wu, wd):
        Bl, Sl, _ = xl.shape
        xt = xl.reshape(-1, D)                       # (T_r, D) my tokens
        T_r = xt.shape[0]
        k = cfg.experts_per_token
        gates, idx, probs = route(cfg, router_w, xt)
        flat_idx = idx.reshape(-1)                   # (T_r*k,)
        dst = flat_idx // e_loc_n                    # destination rank
        # slot within my send-buffer page for rank `dst`
        slot = _arrival_slots(dst, n_model)
        cap = max(8, -(-int(T_r * k * cfg.capacity_factor) // n_model // 8) * 8)
        keep = slot < cap
        slot_c = jnp.minimum(slot, cap - 1)
        tok = jnp.arange(T_r * k) // k
        send = jnp.zeros((n_model, cap, D), xt.dtype)
        send = send.at[dst, slot_c].add(
            xt[tok] * keep[:, None].astype(xt.dtype)
        )
        # metadata rides along: local expert id (+1, 0 = empty slot)
        meta = jnp.zeros((n_model, cap), jnp.int32)
        meta = meta.at[dst, slot_c].add(
            jnp.where(keep, (flat_idx % e_loc_n) + 1, 0)
        )
        recv = jax.lax.all_to_all(send, model, 0, 0, tiled=True)
        meta_r = jax.lax.all_to_all(meta, model, 0, 0, tiled=True)
        # dispatch received tokens into per-expert capacity buffers
        h = recv.reshape(-1, D)                      # (n*cap, D)
        le = meta_r.reshape(-1)                      # 0 = empty
        valid = le > 0
        le0 = jnp.maximum(le - 1, 0)
        slot2 = _arrival_slots(le0, e_loc_n, mask=valid)
        cap2 = max(8, -(-n_model * cap // e_loc_n // 8) * 8)
        keep2 = valid & (slot2 < cap2)
        slot2_c = jnp.minimum(slot2, cap2 - 1)
        buf = jnp.zeros((e_loc_n, cap2, D), h.dtype)
        buf = buf.at[le0, slot2_c].add(
            h * keep2[:, None].astype(h.dtype)
        )
        out = expert_ffn(wg, wu, wd, buf)            # (E_loc, cap2, D)
        back = out[le0, slot2_c]                     # (n*cap, D)
        back = back * keep2[:, None].astype(back.dtype)
        back = back.reshape(n_model, cap, D)
        ret = jax.lax.all_to_all(back, model, 0, 0, tiled=True)
        # combine at home: gather each (t, k) copy from its send slot
        got = ret[dst, slot_c] * keep[:, None].astype(ret.dtype)
        got = got * gates.reshape(-1)[:, None].astype(got.dtype)
        y = jnp.zeros((T_r, D), xt.dtype).at[tok].add(got.astype(xt.dtype))
        aux = load_balance_loss(cfg, probs, idx)
        aux = jax.lax.pmean(aux, model)
        if ctx.batch_axes:
            aux = jax.lax.pmean(aux, ctx.batch_axes)
        return y.reshape(Bl, Sl, D), aux

    x_spec = ctx.spec("batch", "model", None, shape=x.shape)
    y, aux = jax.shard_map(
        body,
        mesh=ctx.mesh,
        in_specs=(
            x_spec,
            P(),
            P(model, None, None),
            P(model, None, None),
            P(model, None, None),
        ),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, p["router"], p["experts_w_gate"], p["experts_w_up"], p["experts_w_down"])
    return y.astype(x.dtype), aux


def moe_apply(cfg: ModelConfig, p, x, ctx: ShardCtx = ShardCtx()):
    """The routed experts by ``ctx``'s dispatch, plus the shared experts
    (if the layer has any)."""
    y, aux = _routed_apply(cfg, p, x, ctx)
    if "shared" in p:
        y = y + shared_experts(p, x).astype(y.dtype)
    return y, aux


def _routed_apply(cfg: ModelConfig, p, x, ctx: ShardCtx):
    dispatch = getattr(ctx, "moe_dispatch", "psum")
    if ctx.mesh is not None and ctx.model_axis is not None:
        if dispatch == "grouped":
            raise ValueError(
                "moe_dispatch='grouped' is the single-device capacity path; "
                "use 'psum' or 'a2a' on a mesh with a model axis"
            )
        if dispatch == "a2a":
            return moe_apply_a2a(cfg, p, x, ctx)
        return moe_apply_sharded(cfg, p, x, ctx)
    if dispatch == "grouped":
        return moe_apply_grouped(
            cfg, p, x, capacity=getattr(ctx, "moe_capacity", None)
        )
    return moe_apply_local(cfg, p, x)
