"""The model module of ``MixtralForCausalLM``: everything the benchmark
knows of Mixtral's shapes and mathematics (the interface is in
``bench/manifest.py``).

Weights.  One jitted call draws one layer (attention, norms, router and
the expert stacks) from the seed and the layer's index, in the type it is
served in; another draws the embedding, the final norm and the head.  The
benchmark hands the program these arrays, and the reference draws each
layer again with the same call when it needs it: both see the same
numbers, and the reference never reads what the program holds.  Values
are uniform with variance 1/fan_in (norm scales uniform in [0.8, 1.2]),
so the residual stream and the logits keep unit scale through the depth
and every norm scale takes part in the result.  Layout: the program's
parameter tree (``models/model.py``): ``embed`` (V, D), ``final_norm``
(D,), ``lm_head`` (D, V) and ``layers`` = [one tree stacked over the
layers], each layer ``{norm1, attn: {wq, wk, wv, wo}, norm2, moe: {router,
experts_w_gate, experts_w_up, experts_w_down}}``.

The plain reference: a Mixtral forward pass in float32, written from the
published architecture (arXiv:2401.04088 and the model's ``config.json``),
in straightforward ``jax.numpy`` at ``Precision.HIGHEST``: RMSNorm,
grouped-query causal attention with rotate-half RoPE at ``rope_theta``, a
softmax router whose top-k gates are renormalised, SiLU-gated experts,
and an untied head.  It imports nothing of the program and reads none of
its state: each layer's weights are drawn again from the seed when the
layer runs, then freed, so the pass fits beside nothing else on the chip.
``judge`` reads served tokens: for every served token, how far its
reference logit lies below the reference's best logit at that position.
With ``control`` it also reads the control at the same positions: the
reference computed in the next precision below the configuration's bf16,
every matmul input rounded to float8 (e4m3, scaled per row and per
tensor) and accumulated in float32.

Work.  Counts are of the work the tokens need: the matmuls of the
projections, of the router and of the ``top_k`` experts each token is
routed to, the attention over the keys a token attends, and the head
where a token's logits are produced.  Padding, capacity rows and experts
a token is not routed to never enter, so a kernel or a step is judged
against the same work however the program lays it out.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

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


def dims(config: Dict) -> Dims:
    heads = int(config["num_attention_heads"])
    d = int(config["hidden_size"])
    return Dims(
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


def check_program(cfg, dims: Dims, config: Dict) -> None:
    """Raise unless the program's ModelConfig ``cfg`` serves the published
    widths the reference reads."""
    have = (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.moe_d_ff, cfg.num_experts,
            cfg.experts_per_token, cfg.vocab_size, cfg.norm_eps,
            cfg.rope_theta, cfg.tie_embeddings)
    want = (dims.layers, dims.d, dims.heads, dims.kv_heads, dims.head_dim,
            dims.d_ff, dims.experts, dims.top_k, dims.vocab, dims.eps,
            dims.theta, bool(config.get("tie_word_embeddings", False)))
    if have != want:
        raise ValueError(f"program config {have} differs from the "
                         f"published {want}")


# -- weights -----------------------------------------------------------------

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


# -- the plain reference and its control -------------------------------------

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
PAD = 512           # sequences are padded to a multiple of this


def _fp8(x, axis):
    """``x`` rounded to float8 e4m3 under a scale that maps its largest
    magnitude along ``axis`` to e4m3's largest, back in float32."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def mm(a, b, low: bool):
    """``a @ b`` in float32; ``low`` rounds both inputs to float8 first."""
    if low:
        a, b = _fp8(a, -1), _fp8(b, None)
    return jnp.matmul(a, b, precision=HI)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """Rotate-half RoPE over (S, heads, head_dim) at positions 0..S-1."""
    S, _, hd = x.shape
    inv = theta ** (-jnp.arange(0, hd // 2, dtype=jnp.float32) / (hd // 2))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnums=(0, 3))
def layer(dims: Dims, p, x, low: bool):
    """One decoder layer over one sequence x (S, D), float32."""
    S = x.shape[0]
    H, K, hd = dims.heads, dims.kv_heads, dims.head_dim
    h = rms_norm(x, p["norm1"], dims.eps)
    q = rope(mm(h, p["attn"]["wq"], low).reshape(S, H, hd), dims.theta)
    k = rope(mm(h, p["attn"]["wk"], low).reshape(S, K, hd), dims.theta)
    v = mm(h, p["attn"]["wv"], low).reshape(S, K, hd)
    kv_of = jnp.arange(H) // (H // K)          # query head -> its KV head
    k, v = k[:, kv_of], v[:, kv_of]            # (S, H, hd)
    qh, kh, vh = (jnp.swapaxes(t, 0, 1) for t in (q, k, v))   # (H, S, hd)
    if low:
        qh, kh, vh = _fp8(qh, -1), _fp8(kh, None), _fp8(vh, None)
    s = jnp.einsum("hqd,hkd->hqk", qh, kh, precision=HI) * hd ** -0.5
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    pr = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    if low:
        pr = _fp8(pr, -1)
    o = jnp.einsum("hqk,hkd->qhd", pr, vh, precision=HI).reshape(S, H * hd)
    x = x + mm(o, p["attn"]["wo"], low)
    h = rms_norm(x, p["norm2"], dims.eps)
    probs = jax.nn.softmax(mm(h, p["moe"]["router"], low), axis=-1)
    top, idx = jax.lax.top_k(probs, dims.top_k)
    top = top / jnp.sum(top, -1, keepdims=True)
    gate = jnp.zeros_like(probs).at[jnp.arange(S)[:, None], idx].set(top)
    y = jnp.zeros_like(x)
    for e in range(dims.experts):
        g = mm(h, p["moe"]["experts_w_gate"][e], low)
        u = mm(h, p["moe"]["experts_w_up"][e], low)
        y = y + gate[:, e:e + 1] * mm(jax.nn.silu(g) * u,
                                      p["moe"]["experts_w_down"][e], low)
    return x + y


@functools.partial(jax.jit, static_argnums=(0, 3))
def logits(dims: Dims, base, x, low: bool):
    return mm(rms_norm(x, base["final_norm"], dims.eps), base["lm_head"], low)


@functools.partial(jax.jit, static_argnums=(0,))
def served_gap(dims: Dims, base, x, tokens):
    """Per position: the reference's best logit minus its logit of
    ``tokens``."""
    lg = logits(dims, base, x, False)
    pick = jnp.take_along_axis(lg, tokens[:, None], axis=-1)[:, 0]
    return jnp.max(lg, axis=-1) - pick


@functools.partial(jax.jit, static_argnums=(0,))
def control_gap(dims: Dims, base, x, x_low):
    """Per position: the reference's best logit minus its logit of the
    token the control (``x_low``, computed in float8) puts first."""
    choice = jnp.argmax(logits(dims, base, x_low, True), axis=-1)
    lg = logits(dims, base, x, False)
    pick = jnp.take_along_axis(lg, choice[:, None], axis=-1)[:, 0]
    return jnp.max(lg, axis=-1) - pick


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def padded(n: int, length: int) -> int:
    """A sequence of ``n`` tokens is right-padded to the next multiple of
    PAD, at most ``length``: a run compiles at most length / PAD shapes,
    and a short sequence does not pay for the longest."""
    return min(-(-n // PAD) * PAD, length)


def hidden(dims: Dims, seed: int, seqs: Sequence[np.ndarray], length: int,
           low: bool = False):
    """The embedding tables and each token sequence's last hidden states
    (padded length, D), layer by layer: one layer's weights live at a
    time.  Causal attention keeps the pads out of every real position."""
    key = base_key(seed)
    base = _f32(draw_base(key, dims))
    xs = [base["embed"][jnp.asarray(np.pad(s, (0, padded(len(s), length)
                                                - len(s))))]
          for s in seqs]
    for li in range(dims.layers):
        p = _f32(draw_layer(key, li, dims))
        xs = [layer(dims, p, x, low) for x in xs]
        del p
    return base, xs


def sequences(served: Sequence[Tuple[np.ndarray, Sequence[int]]]):
    """(prompt, served tokens) pairs -> the token sequences to run, and the
    positions whose logits chose each served token."""
    seqs, where = [], []
    for prompt, toks in served:
        seqs.append(np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
                    .astype(np.int32))
        where.append(np.arange(len(prompt) - 1, len(prompt) - 1 + len(toks)))
    return seqs, where


def judge(dims: Dims, seed: int, served, length: int, control: bool = False
          ) -> Tuple[List[np.ndarray], Optional[List[np.ndarray]]]:
    """For each judged request, the gap of every served token: the
    reference's best logit minus its logit of the served token (0 where
    the program chose the reference's choice).  With ``control``, also the
    control's gaps at the same positions of the same prompts and tokens:
    the gap of the token the float8 forward puts first."""
    seqs, where = sequences(served)
    base, xs = hidden(dims, seed, seqs, length)
    lows = hidden(dims, seed, seqs, length, low=True)[1] if control else None
    prog, ctl = [], []
    for i, (pos, (_, toks)) in enumerate(zip(where, served)):
        fed = np.zeros(xs[i].shape[0], np.int32)
        fed[pos] = np.asarray(toks, np.int32)
        prog.append(np.asarray(served_gap(dims, base, xs[i],
                                          jnp.asarray(fed)))[pos])
        if control:
            ctl.append(np.asarray(control_gap(dims, base, xs[i],
                                              lows[i]))[pos])
    return prog, (ctl if control else None)


# -- work --------------------------------------------------------------------

def layer_weight_flops(dims: Dims) -> int:
    """FLOPs of one token through one layer's weight matmuls."""
    q = dims.heads * dims.head_dim
    kv = dims.kv_heads * dims.head_dim
    attn = dims.d * q + 2 * dims.d * kv + q * dims.d
    router = dims.d * dims.experts
    experts = dims.top_k * 3 * dims.d * dims.d_ff
    return 2 * (attn + router + experts)


def attention_flops(dims: Dims, keys: int) -> int:
    """FLOPs of one query attending ``keys`` keys in one layer (scores
    and the weighted sum of values)."""
    return 4 * keys * dims.heads * dims.head_dim


def head_flops(dims: Dims) -> int:
    return 2 * dims.d * dims.vocab


def token_flops(dims: Dims, keys: int) -> int:
    """One token's forward pass, without the head: every layer's weights
    and attention over ``keys`` keys (itself included)."""
    return dims.layers * (layer_weight_flops(dims)
                          + attention_flops(dims, keys))


def prefill_flops(dims: Dims, n: int) -> int:
    """A prompt of ``n`` tokens (causal: position p attends p + 1 keys),
    and the head on its last token."""
    weights = n * dims.layers * layer_weight_flops(dims)
    attn = dims.layers * attention_flops(dims, 1) * n * (n + 1) // 2
    return weights + attn + head_flops(dims)


def decode_flops(dims: Dims, pos: int) -> int:
    """One generated token fed at position ``pos`` (attends pos + 1 keys),
    with its head."""
    return token_flops(dims, pos + 1) + head_flops(dims)


def expert_ffn_work(dims: Dims, copies: int, experts_hit: int,
                    itemsize: int = 2):
    """(FLOPs, bytes) one grouped expert FFN call needs for ``copies``
    routed token copies over ``experts_hit`` experts: three matmuls per
    copy, each hit expert's three weight matrices read once, each copy's
    row read in and written out."""
    flops = copies * 3 * 2 * dims.d * dims.d_ff
    weights = experts_hit * 3 * dims.d * dims.d_ff * itemsize
    rows = copies * 2 * dims.d * itemsize
    return flops, weights + rows
