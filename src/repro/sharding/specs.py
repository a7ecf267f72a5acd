"""Logical-axis sharding rules.

``ShardCtx`` carries the physical mesh plus the mapping from the two logical
axes the model code uses — ``'batch'`` (data parallel, possibly spanning the
``pod`` axis) and ``'model'`` (tensor/expert parallel) — to mesh axis names.
All model code expresses shardings in logical terms; a ``ShardCtx()`` with no
mesh turns every annotation into a no-op so the same code runs on one CPU
device in tests.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size


@dataclass(frozen=True)
class ShardCtx:
    """Mesh context threaded through model code.  Hashable and static."""

    mesh: Optional[Mesh] = None
    batch_axes: Tuple[str, ...] = ()      # e.g. ('data',) or ('pod', 'data')
    model_axis: Optional[str] = None      # e.g. 'model'
    seq_shard: bool = False               # sequence-parallel residual stream
    # MoE execution path (see models/moe.py): 'psum' | 'a2a' pick the
    # expert-parallel collective on a mesh; 'grouped' selects the
    # single-device capacity-bucketed grouped dispatch (the engine's path).
    moe_dispatch: str = "psum"
    # per-expert capacity override for the grouped path (None: the
    # capacity_factor-based default).  The engine's grouped prefill sets
    # this to the micro-batch token count so no routed copy is dropped.
    moe_capacity: Optional[int] = None

    @property
    def model_size(self) -> int:
        if self.mesh is None or self.model_axis is None:
            return 1
        return self.mesh.shape[self.model_axis]

    @property
    def batch_size(self) -> int:
        if self.mesh is None:
            return 1
        return _axis_size(self.mesh, self.batch_axes)

    def resolve(self, logical) -> Optional[Tuple[str, ...]]:
        if logical is None:
            return None
        if logical == "batch":
            return self.batch_axes or None
        if logical == "model":
            return (self.model_axis,) if self.model_axis else None
        raise ValueError(f"unknown logical axis {logical!r}")

    def spec(self, *logical_axes, shape: Sequence[int] | None = None) -> P:
        """PartitionSpec from logical per-dim axes, dropping non-divisible dims."""
        out = []
        for i, la in enumerate(logical_axes):
            phys = self.resolve(la)
            if phys is not None and shape is not None:
                size = _axis_size(self.mesh, phys)
                if shape[i] % size != 0:
                    phys = None
            out.append(phys if phys is None else tuple(phys))
        # PartitionSpec wants strings or tuples
        cleaned = [a[0] if (a is not None and len(a) == 1) else a for a in out]
        return P(*cleaned)

    def shard(self, x: jax.Array, *logical_axes) -> jax.Array:
        """with_sharding_constraint in logical axes; no-op without a mesh."""
        if self.mesh is None:
            return x
        spec = self.spec(*logical_axes, shape=x.shape)
        return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))

    def shard_residual(self, x: jax.Array) -> jax.Array:
        """Residual stream (B, S, D): optionally sequence-parallel over the
        model axis (Megatron-SP style) to bound per-device activation
        memory in deep-model training."""
        if self.seq_shard:
            return self.shard(x, "batch", "model", None)
        return self.shard(x, "batch", None, None)

    def named(self, spec: P) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, spec)


# ---------------------------------------------------------------------------
# Parameter sharding rules
# ---------------------------------------------------------------------------
def _rule_for(path: str, shape: Tuple[int, ...], zero1: bool) -> Tuple:
    """Return logical axes per dim for a parameter identified by its path.

    ``zero1`` additionally shards a replicated large dim over 'batch'
    (ZeRO-1 style) — used for training so optimizer state is partitioned.
    """
    d = None  # replicated marker
    data = "batch" if zero1 else None

    def dims(*axes):
        return tuple(axes)

    if len(shape) == 0 or "norm" in path or path.endswith("scale") or path.endswith("bias_norm"):
        return dims(*([d] * len(shape)))
    # MoE expert stacks: (E, in, out) — expert parallelism on dim 0
    if "experts" in path and len(shape) == 3:
        if "w_down" in path:
            return dims("model", d, data)
        return dims("model", data, d)
    if "router" in path:
        return dims(data, d)[: len(shape)]
    if "embed" in path:
        return dims(d, "model")          # (V, D): shard D
    if "lm_head" in path:
        return dims(data, "model")       # (D, V): shard V
    # attention projections
    if any(k in path for k in ("wq", "wk", "wv")):
        if len(shape) == 1:              # bias (H*hd,)
            return dims("model")
        return dims(data, "model")       # (D, H*hd)
    if "wo" in path:
        return dims("model", data)       # (H*hd, D)
    # dense FFN
    if any(k in path for k in ("w_gate", "w_up")):
        return dims(data, "model")
    if "w_down" in path:
        return dims("model", data)
    # SSM projections
    if any(k in path for k in ("wz", "wx", "wB", "wC", "wdt", "in_proj")):
        return dims(data, "model")[: len(shape)]
    if "out_proj" in path:
        return dims("model", data)
    if "conv" in path:
        return dims(d, "model")[: len(shape)]  # (width, channels)
    if path.endswith("A_log") or path.endswith("D") or path.endswith("dt_bias"):
        return dims("model")[: len(shape)]
    return dims(*([d] * len(shape)))


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_shardings(ctx: ShardCtx, params, *, zero1: bool = False, stacked_dims: int = 1):
    """Tree of NamedShardings (or None without mesh) for a param pytree.

    ``stacked_dims`` — number of leading scan-stacking dims (layer groups)
    that are never sharded.
    """

    def one(path, leaf):
        if ctx.mesh is None:
            return None
        pstr = _path_str(path)
        shape = leaf.shape
        # only the per-layer stack ('layers/...') carries leading group dims
        n_lead = stacked_dims if pstr.startswith("layers") else 0
        n_lead = min(n_lead, max(0, len(shape) - 1))
        core_shape = shape[n_lead:]
        logical = _rule_for(pstr, core_shape, zero1)
        # expert count not divisible by the model axis => tensor-parallel
        # experts instead of expert parallelism (shard the hidden dim)
        if (
            "experts" in pstr
            and len(core_shape) == 3
            and core_shape[0] % max(ctx.model_size, 1) != 0
        ):
            if "w_down" in pstr:
                logical = (None, "model", "batch" if zero1 else None)
            else:
                logical = (None, "batch" if zero1 else None, "model")
        logical = tuple([None] * n_lead) + tuple(logical)
        spec = ctx.spec(*logical, shape=shape)
        return NamedSharding(ctx.mesh, spec)

    return jax.tree_util.tree_map_with_path(one, params)


def cache_shardings(ctx: ShardCtx, cache):
    """Shardings for decode caches.

    KV leaves (G, B, S, K, hd): batch over data; KV heads over model when
    divisible, else head_dim over model.  SSM state (G, B, nh, ns, hp):
    heads over model.  Conv state (G, B, W, ch): channels over model.
    """

    def one(path, leaf):
        if ctx.mesh is None:
            return None
        name = _path_str(path)
        shape = leaf.shape
        msize = max(ctx.model_size, 1)
        if name.endswith("conv"):
            logical = (None, "batch", None, "model")
        elif name.endswith("k") or name.endswith("v"):
            if shape[3] % msize == 0:
                # KV heads shard over the model axis
                logical = (None, "batch", None, "model", None)
            elif shape[2] % msize == 0:
                # context parallelism: cache sequence over the model axis
                logical = (None, "batch", "model", None, None)
            else:
                logical = (None, "batch", None, None, "model")
        elif name.endswith("h"):
            logical = (None, "batch", "model", None, None)
        else:
            logical = tuple([None] * len(shape))
        spec = ctx.spec(*logical, shape=shape)
        return NamedSharding(ctx.mesh, spec)

    return jax.tree_util.tree_map_with_path(one, cache)
