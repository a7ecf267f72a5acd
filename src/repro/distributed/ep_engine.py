"""Expert-parallel MoE decode stage: pipelined all-to-all over the mesh.

The single-device engine runs the decode MoE stage as ONE launch
(``core.engine._grouped_expert_math``): norm2 -> route -> gather by expert
(``models.moe.ragged_dispatch``, which computes what the capacity-bucketed
``(E, C, D)`` ``grouped_dispatch`` computes) -> grouped FFN ->
gate-weighted scatter-add.  This
module is the mesh realization of the SAME stage for an engine whose
``ShardCtx`` carries a ``model`` axis:

* ``moe_dispatch='a2a'`` (``_ep_a2a_expert_module``) — tokens are sharded
  over the model axis; each rank routes its T/n tokens, ships every routed
  copy once to the rank owning its expert (``jax.lax.all_to_all``), runs the
  LOCAL ``(E/n, C_loc, D)`` grouped FFN, and a second all-to-all returns the
  outputs home where they are gate-weighted and scatter-added in the exact
  per-copy order of the single-device path.  The accumulated batch is split
  into ``chunks`` pipeline chunks with NO data dependence between them, so
  chunk *k+1*'s all-to-all can overlap chunk *k*'s expert FFN (EPS-MoE);
  ``serial=True`` threads an ``optimization_barrier`` between chunks to
  forbid exactly that overlap (the benchmark baseline — barriers are
  value-identity, so serial and pipelined outputs are bitwise equal).

  When capacity admits every routed token, every copy's FFN row, gate
  product and per-token add order match ``grouped_dispatch`` exactly, so
  the stage is bit-identical to the single-device grouped path.  Under
  capacity pressure the DROP SETS differ (slots are assigned per chunk at
  the expert owner, not over the full flat batch) — same contract class,
  different victims.

* ``moe_dispatch='psum'`` (``_ep_psum_expert_module``) — tokens replicated;
  every rank computes the single-device routing + full-batch arrival slots
  (drop decisions identical to single-device), runs only its LOCAL experts'
  share of the ``(E, C, D)`` buffer, and the partial outputs are summed
  with a ``psum``.  The cross-rank sum reassociates each token's k-copy
  addition, so this path is allclose- (not bit-) identical.

Collectives in this package live inside ``register_jit``-registered modules
only — rule MG107 in ``repro.analysis.lint`` enforces it.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.analysis.registry import register_jit
from repro.configs.base import ModelConfig
from repro.models import moe as moe_mod
from repro.models.layers import rms_norm
from repro.sharding.specs import ShardCtx


# ---------------------------------------------------------------------------
# Static helpers (no device code)
# ---------------------------------------------------------------------------
def pipeline_chunks(t_local: int, requested: int) -> int:
    """Largest chunk count <= ``requested`` that divides the per-rank token
    count — chunked dispatch needs equal static chunk shapes."""
    c = max(1, min(int(requested), max(1, t_local)))
    while t_local % c:
        c -= 1
    return c


def a2a_bytes_per_stage(cfg: ModelConfig, T: int, n_model: int,
                        itemsize: int = 4) -> int:
    """Interconnect bytes one a2a MoE stage moves for a T-token batch:
    every routed copy crosses twice (dispatch + return) at D activation
    bytes plus one int32 metadata lane on dispatch.  Independent of the
    pipeline chunk count — chunking re-times the traffic, not its volume.
    Counts full buffer bytes (including each rank's self-share) so the
    number is comparable across mesh shapes."""
    if n_model <= 1:
        return 0
    copies = T * cfg.experts_per_token
    return copies * n_model * (2 * cfg.d_model * itemsize + 4)


def validate_ep_shard(cfg: ModelConfig, sctx: ShardCtx) -> int:
    """The mesh-engine construction contract; returns the model-axis size.

    Raises ``ValueError`` for combos the collective decode stage does not
    support — the ``ShardCtx.moe_dispatch`` threading bugfix makes these
    reachable, so they must fail loudly at construction, not mid-decode."""
    if sctx is None:
        return 1                     # no mesh: the single-device contract
    if sctx.mesh is None or sctx.model_axis is None:
        raise ValueError(
            "expert-parallel engine needs a ShardCtx with a mesh and a "
            "model_axis; for single-device serving pass sctx=None"
        )
    n = sctx.model_size
    if cfg.num_shared_experts or cfg.first_k_dense or cfg.mla:
        raise ValueError(
            "the expert-parallel engine serves routed experts behind plain "
            "attention only: shared experts, leading dense layers and "
            "latent attention are served single-device"
        )
    if sctx.moe_dispatch not in ("a2a", "psum"):
        raise ValueError(
            f"moe_dispatch={sctx.moe_dispatch!r} is not a collective "
            "decode path: 'grouped' is the single-device capacity path "
            "(pass sctx=None); use 'a2a' or 'psum' on a mesh"
        )
    if cfg.num_experts % n:
        raise ValueError(
            f"num_experts={cfg.num_experts} is not divisible by the model "
            f"axis size {n}: expert-parallel dispatch shards whole expert "
            "stacks only"
        )
    return n


# ---------------------------------------------------------------------------
# a2a path: token-sharded, capacity-bucketed, pipeline-chunked
# ---------------------------------------------------------------------------
@register_jit("distributed.ep_a2a_expert")
@functools.partial(
    jax.jit,
    static_argnames=("cfg", "mesh", "axis", "chunks", "capacity", "serial",
                     "n_real"),
)
def _ep_a2a_expert_module(cfg, mesh, axis, chunks, capacity, serial, n_real,
                          norm2_w, router_w, wg, wu, wd, x, gates=None,
                          idx=None):
    """The whole mesh MoE stage in one launch; returns ``(y, kept, dropped,
    load)`` with the same meaning as ``engine._grouped_expert_math``, for
    the first ``n_real`` rows of ``y``.

    With ``gates``/``idx`` given, ``x`` is already normalized and routed
    (grouped prefill routes in its mixer launch); the stage then dispatches
    exactly that routing, as the single-device prefill FFN launch does.

    ``x`` is the (T, D) batch with T divisible by the model-axis size; rows
    from ``n_real`` on are padding the caller added to get there.  Their
    copies are never sent, so they take no capacity slot and enter no
    counter, and a padded batch keeps the single-device stage's drops and
    values.  Each rank owns T/n tokens and E/n experts; ``capacity`` is the
    per-expert local buffer depth (the plan's b_e, shared with the
    single-device path)."""
    n = mesh.shape[axis]
    E = cfg.num_experts
    e_loc = E // n
    k = cfg.experts_per_token
    T, D = x.shape

    def body(xl, norm2_w, router_w, wg, wu, wd, *routed):
        T_r = xl.shape[0]
        if routed:
            h, (gates, idx) = xl, routed
        else:
            # identical per-token math to the single-device stage: rms_norm
            # and routing are row-wise, so sharding the batch never
            # changes a row
            h = rms_norm(xl, norm2_w, cfg.norm_eps)
            gates, idx, _ = moe_mod.route(cfg, router_w, h)
        real = lax.axis_index(axis) * T_r + jnp.arange(T_r) < n_real
        t_c = T_r // chunks
        ys, kepts = [], []
        load = jnp.zeros((E,), jnp.int32)
        prev = None
        for c in range(chunks):
            hc = h[c * t_c:(c + 1) * t_c]
            gc = gates[c * t_c:(c + 1) * t_c].reshape(-1)      # (t_c*k,)
            ic = idx[c * t_c:(c + 1) * t_c].reshape(-1)
            if serial and prev is not None:
                # benchmark baseline: tie chunk c's inputs to chunk c-1's
                # output so the compiler cannot overlap their collectives.
                # optimization_barrier is value-identity — serial output
                # stays bitwise equal to the pipelined one.
                hc, _ = lax.optimization_barrier((hc, prev))
            tok = jnp.arange(t_c * k) // k
            sent = real[c * t_c:(c + 1) * t_c][tok]            # (t_c*k,)
            dst = ic // e_loc                                  # owner rank
            # dispatch a2a: one page per destination rank, sized so the
            # send stage never drops (capacity acts at the expert owner)
            cap_s = t_c * k
            slot = moe_mod._arrival_slots(dst, n, mask=sent)
            send = jnp.zeros((n, cap_s, D), hc.dtype)
            send = send.at[dst, slot].add(
                hc[tok] * sent[:, None].astype(hc.dtype)
            )
            meta = jnp.zeros((n, cap_s), jnp.int32)
            meta = meta.at[dst, slot].add(
                jnp.where(sent, ic % e_loc + 1, 0)             # 0 = empty
            )
            recv = lax.all_to_all(send, axis, 0, 0, tiled=True)
            meta_r = lax.all_to_all(meta, axis, 0, 0, tiled=True)
            # local expert bucketing under the shared capacity b_e: the
            # owner sees every rank's copies for this chunk
            hr = recv.reshape(-1, D)                           # (n*cap_s, D)
            le = meta_r.reshape(-1)
            valid = le > 0
            le0 = jnp.maximum(le - 1, 0)
            slot2 = moe_mod._arrival_slots(le0, e_loc, mask=valid)
            cap_l = max(1, min(capacity, n * cap_s))
            keep = valid & (slot2 < cap_l)
            slot2_c = jnp.minimum(slot2, cap_l - 1)
            buf = jnp.zeros((e_loc, cap_l, D), hr.dtype)
            buf = buf.at[le0, slot2_c].add(
                hr * keep[:, None].astype(hr.dtype)
            )
            from repro.kernels import ops as kernel_ops

            out = kernel_ops.grouped_expert_ffn(buf, wg, wu, wd)
            back = out[le0, slot2_c] * keep[:, None].astype(out.dtype)
            # return a2a + combine at home: same per-copy gate product and
            # flat (t, k) scatter-add order as grouped_dispatch
            ret = lax.all_to_all(
                back.reshape(n, cap_s, D), axis, 0, 0, tiled=True
            )
            got = ret[dst, slot] * gc[:, None].astype(ret.dtype)
            y_c = jnp.zeros((t_c, D), hc.dtype).at[tok].add(
                got.astype(hc.dtype)
            )
            prev = y_c
            ys.append(y_c)
            kepts.append(jnp.sum(keep.astype(jnp.int32)))
            load = load + jnp.zeros((E,), jnp.int32).at[ic].add(
                sent.astype(jnp.int32)
            )
        y = jnp.concatenate(ys, axis=0) if len(ys) > 1 else ys[0]
        # each copy is counted once at its expert owner; the psums fold the
        # per-rank partials into the single-device counter semantics
        kept = lax.psum(sum(kepts), axis)
        load = lax.psum(load, axis)
        dropped = jnp.int32(n_real * k) - kept
        return y, kept, dropped, load

    x_spec = P(axis, None)
    rep = P()
    e_spec = P(axis, None, None)
    routed = () if gates is None else (gates, idx)
    y, kept, dropped, load = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(x_spec, rep, rep, e_spec, e_spec, e_spec)
        + (x_spec,) * len(routed),
        out_specs=(x_spec, rep, rep, rep),
        check_vma=False,
    )(x, norm2_w, router_w, wg, wu, wd, *routed)
    return y[:n_real].astype(x.dtype), kept, dropped, load


# ---------------------------------------------------------------------------
# psum path: token-replicated, single-device slotting, partial-sum combine
# ---------------------------------------------------------------------------
@register_jit("distributed.ep_psum_expert")
@functools.partial(
    jax.jit, static_argnames=("cfg", "mesh", "axis", "capacity"),
)
def _ep_psum_expert_module(cfg, mesh, axis, capacity,
                           norm2_w, router_w, wg, wu, wd, x):
    """Replicated-token expert parallelism: full-batch routing and the
    single-device arrival-slot assignment on every rank (drop decisions
    are EXACTLY the single-device ones), each rank computes only its local
    experts' share, partial outputs ``psum`` together.  The cross-rank sum
    reassociates each token's k-copy addition — allclose, not bitwise."""
    n = mesh.shape[axis]
    E = cfg.num_experts
    e_loc = E // n
    k = cfg.experts_per_token
    T, D = x.shape

    def body(xf, norm2_w, router_w, wg, wu, wd):
        r = lax.axis_index(axis)
        h = rms_norm(xf, norm2_w, cfg.norm_eps)
        gates, idx, _ = moe_mod.route(cfg, router_w, h)
        fi = idx.reshape(-1)                                   # (T*k,)
        fg = gates.reshape(-1)
        tok = jnp.arange(T * k) // k
        # single-device slotting over the FULL expert axis: capacity and
        # keep/drop per copy match grouped_dispatch exactly
        slot = moe_mod._arrival_slots(fi, E)
        keep = slot < capacity
        slot_c = jnp.minimum(slot, capacity - 1)
        mine = (fi // e_loc) == r
        fill = keep & mine
        buf = jnp.zeros((e_loc, capacity, D), h.dtype)
        buf = buf.at[fi % e_loc, slot_c].add(
            h[tok] * fill[:, None].astype(h.dtype)
        )
        from repro.kernels import ops as kernel_ops

        out = kernel_ops.grouped_expert_ffn(buf, wg, wu, wd)
        back = out[fi % e_loc, slot_c]
        back = back * (fill[:, None] * fg[:, None]).astype(back.dtype)
        y_r = jnp.zeros((T, D), h.dtype).at[tok].add(back.astype(h.dtype))
        y = lax.psum(y_r, axis)
        kept = lax.psum(jnp.sum(fill.astype(jnp.int32)), axis)
        load = jnp.zeros((E,), jnp.int32).at[fi].add(1)  # replicated math
        return y, kept, jnp.int32(T * k) - kept, load

    rep = P()
    e_spec = P(axis, None, None)
    y, kept, dropped, load = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(rep, rep, rep, e_spec, e_spec, e_spec),
        out_specs=(rep, rep, rep, rep),
        check_vma=False,
    )(x, norm2_w, router_w, wg, wu, wd)
    return y.astype(x.dtype), kept, dropped, load


# ---------------------------------------------------------------------------
# Engine facade
# ---------------------------------------------------------------------------
class ExpertParallelEngine:
    """Convenience facade: ``ExpertParallelEngine(cfg, params, plan, sctx,
    ...)`` IS a ``ModuleBatchingEngine`` whose MoE stage runs the collective
    dispatch.  Kept as a named entry point for discoverability — the same
    engine is reachable by passing ``sctx=`` to ``ModuleBatchingEngine``
    (or ``ServeConfig(sctx=...)`` for serving)."""

    def __new__(cls, cfg, params, plan, sctx: ShardCtx, *,
                ep_chunks: int = 1, ep_serial: bool = False, **kwargs):
        from repro.core.engine import ModuleBatchingEngine

        if sctx is None or sctx.mesh is None or sctx.model_axis is None:
            raise ValueError(
                "ExpertParallelEngine needs a ShardCtx with a mesh and "
                "model_axis; use ModuleBatchingEngine for single-device"
            )
        return ModuleBatchingEngine(
            cfg, params, plan, sctx=sctx, ep_chunks=ep_chunks,
            ep_serial=ep_serial, **kwargs,
        )


# ---------------------------------------------------------------------------
# Engine-facing stage driver
# ---------------------------------------------------------------------------
def _mesh_placed(engine, li: int, p) -> Tuple:
    """The layer's MoE params placed for the mesh launch, cached per layer:
    expert stacks sharded over the model axis (where the engine's
    ``ParamStore`` already built them, so this is a no-op for them),
    norm2/router replicated.  Explicit ``device_put`` — a planned,
    once-per-layer placement, so repeated launches move no bytes and trip
    no transfer guard."""
    cache = engine._ep_params
    ent = cache.get(li)
    moe = p["moe"]
    key = id(moe["experts_w_gate"])
    if ent is not None and ent[0] == key:
        return ent[1]
    sctx = engine.sctx
    rep = NamedSharding(sctx.mesh, P())
    esh = NamedSharding(sctx.mesh, P(sctx.model_axis, None, None))
    placed = (
        jax.device_put(p["norm2"], rep),      # lint: allow[MG105] once-per-layer mesh placement, cached — not streamed htod traffic
        jax.device_put(moe["router"], rep),   # lint: allow[MG105] once-per-layer mesh placement, cached
        jax.device_put(moe["experts_w_gate"], esh),  # lint: allow[MG105] once-per-layer mesh placement, cached
        jax.device_put(moe["experts_w_up"], esh),    # lint: allow[MG105] once-per-layer mesh placement, cached
        jax.device_put(moe["experts_w_down"], esh),  # lint: allow[MG105] once-per-layer mesh placement, cached
    )
    cache[li] = (key, placed)
    return placed


def ep_expert_stage(engine, li: int, p, x, capacity=None, routed=None):
    """Run one MoE layer's collective stage for a mesh engine; returns
    ``(y, kept, dropped, load, a2a_bytes)``.

    ``capacity`` is the per-expert buffer depth (default: the engine's
    decode capacity, the plan's b_e).  ``routed=(h, gates, idx)`` hands in
    ``x``'s normalized rows and their routing, computed already (grouped
    prefill routes in its mixer launch): the a2a stage then dispatches
    exactly that, as the single-device prefill FFN launch does, so the two
    stay bit-identical.  Path selection (the ROADMAP mesh
    contract): ``a2a`` needs the batch divisible by the model-axis size —
    when it is not (odd live batch, a prefill micro-batch), the batch is
    padded with rows the module never sends, so values, drops and counters
    are those of the unpadded batch.  ``psum`` replicates tokens and has no
    divisibility constraint.  A one-rank mesh runs the single-device
    grouped launch, which is the reference both paths must match."""
    from repro.analysis import runtime as sanitizer
    from repro.core import engine as engine_mod

    sctx = engine.sctx
    n = sctx.model_size
    T = x.shape[0]
    cap = engine._expert_capacity(T) if capacity is None else capacity
    if n == 1:
        if routed is not None:
            y, kept, dropped, load, _ = engine_mod._grouped_ffn_module(
                engine.cfg, cap, *routed, p["moe"]["experts_w_gate"],
                p["moe"]["experts_w_up"], p["moe"]["experts_w_down"],
            )
        else:
            y, kept, dropped, load, _ = engine_mod._grouped_expert_module(
                engine.cfg, p, x, cap
            )
        return y, kept, dropped, load, 0
    home = x.sharding
    norm2_w, router_w, wg, wu, wd = _mesh_placed(engine, li, p)
    if sctx.moe_dispatch == "a2a":
        pad = -T % n
        # the engine's buffers are single-device committed arrays; the
        # mesh launch needs its batch sharded over the model axis and
        # hands back mesh-committed outputs — both hops are explicit,
        # planned d2d placements, tagged for the sanitizer report
        rows = NamedSharding(sctx.mesh, P(sctx.model_axis, None))
        batch = (x,) if routed is None else routed
        with sanitizer.allowed("ep-a2a-batch"):
            placed = jax.device_put(  # lint: allow[MG105] planned per-launch d2d batch placement onto the mesh, tagged ep-a2a-batch
                [jnp.pad(a, ((0, pad), (0, 0))) if pad else a
                 for a in batch], rows
            )
        chunks = pipeline_chunks((T + pad) // n, engine.ep_chunks)
        out = _ep_a2a_expert_module(
            engine.cfg, sctx.mesh, sctx.model_axis, chunks, cap,
            engine.ep_serial, T, norm2_w, router_w, wg, wu, wd, *placed,
        )
        nbytes = a2a_bytes_per_stage(
            engine.cfg, T, n, itemsize=x.dtype.itemsize
        )
    else:
        with sanitizer.allowed("ep-a2a-batch"):
            x_m = jax.device_put(      # lint: allow[MG105] planned per-launch d2d batch replication onto the mesh, tagged ep-a2a-batch
                x, NamedSharding(sctx.mesh, P())
            )
        out = _ep_psum_expert_module(
            engine.cfg, sctx.mesh, sctx.model_axis, cap,
            norm2_w, router_w, wg, wu, wd, x_m,
        )
        nbytes = 0
    with sanitizer.allowed("ep-a2a-combine"):
        y = jax.device_put(out[0], home)   # lint: allow[MG105] planned d2d return of the mesh stage's output to the engine's home device, tagged ep-a2a-combine
        dev = next(iter(home.device_set))
        kept, dropped, load = jax.device_put(out[1:], dev)  # lint: allow[MG105] planned d2d return of mesh-side counters, tagged ep-a2a-combine
    return y, kept, dropped, load, nbytes
