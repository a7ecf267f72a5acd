"""Seeded random weights of a Mixtral-style model, made on the device.

One jitted call draws one layer (attention, norms, router and the expert
stacks) from the seed and the layer's index, in the type it is served in;
another draws the embedding, the final norm and the head.  The benchmark
hands the program these arrays, and the reference draws each layer again
with the same call when it needs it: both see the same numbers, and the
reference never reads what the program holds.

Values are uniform with variance 1/fan_in (norm scales uniform in
[0.8, 1.2]), so the residual stream and the logits keep unit scale
through the depth and every norm scale takes part in the result.

Layout: the program's parameter tree (``models/model.py``): ``embed``
(V, D), ``final_norm`` (D,), ``lm_head`` (D, V) and ``layers`` = [one tree
stacked over the layers], each layer ``{norm1, attn: {wq, wk, wv, wo},
norm2, moe: {router, experts_w_gate, experts_w_up, experts_w_down}}``.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class Dims:
    """The published shape of the model, read from its configuration."""

    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    experts: int
    top_k: int
    vocab: int
    eps: float
    theta: float

    @classmethod
    def of(cls, config: Dict) -> "Dims":
        heads = int(config["num_attention_heads"])
        d = int(config["hidden_size"])
        return cls(
            layers=int(config["num_hidden_layers"]), d=d, heads=heads,
            kv_heads=int(config["num_key_value_heads"]),
            head_dim=int(config.get("head_dim") or d // heads),
            d_ff=int(config["intermediate_size"]),
            experts=int(config["num_local_experts"]),
            top_k=int(config["num_experts_per_tok"]),
            vocab=int(config["vocab_size"]),
            eps=float(config["rms_norm_eps"]),
            theta=float(config["rope_theta"]),
        )


def base_key(seed: int) -> jax.Array:
    """The weights' root key: 64 bits of ``seed``'s SeedSequence, so any
    whole number (seeds may exceed 32 bits) gives its own weights."""
    w = np.random.SeedSequence([int(seed), 0]).generate_state(2, np.uint32)
    return jax.random.fold_in(jax.random.PRNGKey(int(w[0])), int(w[1]))


def _uniform(key, shape, fan_in: float, dtype):
    a = (3.0 / fan_in) ** 0.5
    return jax.random.uniform(key, shape, jnp.float32, -a, a).astype(dtype)


def _scale(key, shape, dtype):
    return jax.random.uniform(key, shape, jnp.float32, 0.8, 1.2).astype(dtype)


@functools.partial(jax.jit, static_argnums=(2,))
def draw_layer(key, layer, dims: Dims) -> Dict:
    """Layer ``layer``'s weights (bf16; the router in f32, as served)."""
    k = jax.random.split(jax.random.fold_in(key, 1 + layer), 10)
    d, q = dims.d, dims.heads * dims.head_dim
    kv, e, f = dims.kv_heads * dims.head_dim, dims.experts, dims.d_ff
    bf = jnp.bfloat16
    return {
        "norm1": _scale(k[0], (d,), bf),
        "attn": {"wq": _uniform(k[1], (d, q), d, bf),
                 "wk": _uniform(k[2], (d, kv), d, bf),
                 "wv": _uniform(k[3], (d, kv), d, bf),
                 "wo": _uniform(k[4], (q, d), q, bf)},
        "norm2": _scale(k[5], (d,), bf),
        "moe": {"router": _uniform(k[6], (d, e), d, jnp.float32),
                "experts_w_gate": _uniform(k[7], (e, d, f), d, bf),
                "experts_w_up": _uniform(k[8], (e, d, f), d, bf),
                "experts_w_down": _uniform(k[9], (e, f, d), f, bf)},
    }


@functools.partial(jax.jit, static_argnums=(1,))
def draw_base(key, dims: Dims) -> Dict:
    k = jax.random.split(jax.random.fold_in(key, 0), 3)
    bf = jnp.bfloat16
    return {"embed": _uniform(k[0], (dims.vocab, dims.d), dims.d, bf),
            "final_norm": _scale(k[1], (dims.d,), bf),
            "lm_head": _uniform(k[2], (dims.d, dims.vocab), dims.d, bf)}


class LayerStack:
    """A leaf of the program's layer-stacked tree whose layers are separate
    arrays: ``stack[g]`` is layer ``g``'s array.  The program takes its
    layers apart with ``leaf[g]`` (``serving/weights.unstack_layers``), so
    resident weights drawn layer by layer on the device reach it without a
    stacked copy beside them, which would not fit the chip twice."""

    def __init__(self, arrays: List) -> None:
        self.arrays = arrays
        self.shape = (len(arrays),) + tuple(arrays[0].shape)
        self.dtype = arrays[0].dtype

    def __getitem__(self, g):
        return self.arrays[g]


def resident_params(seed: int, dims: Dims) -> Dict:
    """Every weight on the device, drawn there layer by layer."""
    key = base_key(seed)
    layers = [draw_layer(key, li, dims) for li in range(dims.layers)]
    stacked = jax.tree.map(lambda *a: LayerStack(list(a)), *layers)
    params = dict(draw_base(key, dims))
    params["layers"] = [stacked]
    jax.block_until_ready(jax.tree.leaves(layers))
    return params


def host_params(seed: int, dims: Dims) -> Dict:
    """Base weights on the device; every layer drawn on the device and
    copied into host memory (numpy), where the program streams it from."""
    key = base_key(seed)
    host = None
    for li in range(dims.layers):
        layer = jax.device_get(draw_layer(key, li, dims))
        if host is None:
            host = jax.tree.map(
                lambda a: np.empty((dims.layers,) + a.shape, a.dtype), layer)
        for dst, src in zip(jax.tree.leaves(host), jax.tree.leaves(layer)):
            dst[li] = src
        del layer
    params = dict(draw_base(key, dims))
    params["layers"] = [host]
    return params
