"""The model module of ``DeepseekV2ForCausalLM``: everything the benchmark
knows of DeepSeek-V2's shapes and mathematics (the interface is in
``bench/manifest.py``).

Weights.  One jitted call draws one layer (latent attention, norms and
either the dense MLP or the router, the routed expert stacks and the
shared experts) from the seed and the layer's index, in the type it is
served in; another draws the embedding, the final norm and the head.  The
benchmark hands the program these arrays, and the reference draws each
layer again with the same call when it needs it: both see the same
numbers, and the reference never reads what the program holds.  Values
are uniform with variance 1/fan_in (norm scales uniform in [0.8, 1.2]),
as for Mixtral.  Layout: the program's parameter tree
(``models/model.py``): ``embed`` (V, D), ``final_norm`` (D,), ``lm_head``
(D, V), ``lead`` = [one dict per leading dense layer] and ``layers`` =
[one tree stacked over the MoE layers].  A layer is ``{norm1, mla: {wq,
wkv_a, kv_norm, wkv_b, wo}, norm2}`` with ``ffn: {w_gate, w_up, w_down}``
(dense) or ``moe: {router, experts_w_gate, experts_w_up, experts_w_down,
shared: {w_gate, w_up, w_down}}``; the projections keep the published
column order (``q_proj`` per head nope then rope, ``kv_a_proj_with_mqa``
latent then rope key, ``kv_b_proj`` per head nope key then value).

The plain reference: a DeepSeek-V2 forward pass in float32, written from
the published ``modeling_deepseek.py`` that ships beside the model's
``config.json`` (huggingface.co/deepseek-ai/DeepSeek-V2-Lite), in
straightforward ``jax.numpy`` at ``Precision.HIGHEST``.  It imports
nothing of the program and reads none of its state:

* RMSNorm before attention and before the FFN, residual adds after each.
* Multi-head latent attention without q-LoRA, in the decompressed form
  (no absorption): queries from ``q_proj``; the latent and the shared
  rope key from ``kv_a_proj_with_mqa``; the latent normalised
  (``kv_a_layernorm``) and up-projected by ``kv_b_proj`` into every head's
  nope key and value; the rope key broadcast to every head.
* yarn RoPE on the rope dims (``DeepseekV2YarnRotaryEmbedding``): the
  frequencies interpolated by ``factor`` below the correction range set by
  ``beta_fast``/``beta_slow`` at ``original_max_position_embeddings``, the
  original ones above it, a linear ramp between; cos and sin times
  ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``.  Before the
  rotate-half the rope dims are de-interleaved (evens, then odds), as the
  published ``apply_rotary_pos_emb`` does.  The softmax scale is
  ``q_head_dim ** -0.5`` times ``mscale(factor, mscale_all_dim) ** 2``.
* Layers below ``first_k_dense_replace``: a dense SiLU MLP of
  ``intermediate_size``.  Every later layer: a softmax router in float32,
  greedy top-k, the top-k weights not renormalised
  (``norm_topk_prob`` false) and times ``routed_scaling_factor``; the
  routed SiLU experts, computed for every token and weighted by its gate
  (zero for experts it is not routed to); plus the shared experts, one
  SiLU MLP of ``n_shared_experts * moe_intermediate_size`` on every token.
* An untied head after the final norm.

Departures from the published code: weights are random (see above); the
router's auxiliary loss and ``seq_aux`` only shape training and are left
out; ``n_group``/``topk_group`` are 1, so the group-limited routing is
plain greedy top-k; padding positions are right of every real position
and causal masking keeps them out.  Each layer's weights are drawn again
from the seed when the layer runs, then freed.  ``judge`` reads served
tokens: for every served token, how far its reference logit lies below
the reference's best logit at that position.  With ``control`` it also
reads the control at the same positions: the reference computed in the
next precision below the configuration's bf16, every matmul input rounded
to float8 (e4m3, scaled per row and per tensor) and accumulated in
float32.

Work.  Counts are of the work the tokens need: the projections, the
router, the ``top_k`` routed experts each token is sent to, the shared
experts, the dense MLP, attention over the keys a token attends, and the
head where logits are produced.  Decode is counted in the absorbed form
the program serves (W_UK folded into the query, W_UV applied after the
heads attend the cached latent rows: no cached row is up-projected);
prefill in the decompressed form (every token's keys and values
up-projected once, attention per head at the q/k and v head widths).
Padding, capacity rows and experts a token is not routed to never enter.
"""
from __future__ import annotations

import functools
import math
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
    kv_rank: int
    nope: int
    rope: int
    v: int
    d_ff: int               # the leading dense layers' MLP
    moe_ff: int             # one routed or shared expert
    experts: int            # routed experts
    top_k: int
    shared: int
    first_dense: int
    vocab: int
    eps: float
    theta: float
    norm_topk: bool
    scaling: float
    yarn_factor: float
    yarn_mscale: float
    yarn_mscale_all: float
    yarn_original: int
    yarn_beta_fast: float
    yarn_beta_slow: float

    @property
    def cache_width(self) -> int:
        """Values one token caches per layer: the latent and the rope key."""
        return self.kv_rank + self.rope


def dims(config: Dict) -> Dims:
    if config.get("q_lora_rank"):
        raise ValueError("q-LoRA is not served; this module reads "
                         "q_lora_rank null")
    rs = config.get("rope_scaling") or {}
    if rs and rs.get("type") != "yarn":
        raise ValueError(f"rope_scaling {rs.get('type')!r}: only yarn")
    if config.get("scoring_func", "softmax") != "softmax" or \
            config.get("topk_method", "greedy") != "greedy":
        raise ValueError("only softmax scores with greedy top-k")
    return Dims(
        layers=int(config["num_hidden_layers"]),
        d=int(config["hidden_size"]),
        heads=int(config["num_attention_heads"]),
        kv_rank=int(config["kv_lora_rank"]),
        nope=int(config["qk_nope_head_dim"]),
        rope=int(config["qk_rope_head_dim"]),
        v=int(config["v_head_dim"]),
        d_ff=int(config["intermediate_size"]),
        moe_ff=int(config["moe_intermediate_size"]),
        experts=int(config["n_routed_experts"]),
        top_k=int(config["num_experts_per_tok"]),
        shared=int(config.get("n_shared_experts") or 0),
        first_dense=int(config.get("first_k_dense_replace", 0)),
        vocab=int(config["vocab_size"]),
        eps=float(config["rms_norm_eps"]),
        theta=float(config["rope_theta"]),
        norm_topk=bool(config.get("norm_topk_prob", False)),
        scaling=float(config.get("routed_scaling_factor", 1.0)),
        yarn_factor=float(rs.get("factor", 0.0)),
        yarn_mscale=float(rs.get("mscale", 1.0)),
        yarn_mscale_all=float(rs.get("mscale_all_dim", 0.0)),
        yarn_original=int(rs.get("original_max_position_embeddings", 4096)),
        yarn_beta_fast=float(rs.get("beta_fast", 32)),
        yarn_beta_slow=float(rs.get("beta_slow", 1)),
    )


def check_program(cfg, dims: Dims, config: Dict) -> None:
    """Raise unless the program's ModelConfig ``cfg`` serves the published
    widths and mechanisms the reference reads."""
    have = (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.kv_lora_rank,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
            cfg.d_ff, cfg.moe_d_ff, cfg.num_experts, cfg.experts_per_token,
            cfg.num_shared_experts, cfg.first_k_dense, cfg.moe_layer_period,
            cfg.vocab_size, cfg.norm_eps, cfg.rope_theta, cfg.norm_topk_prob,
            1.0, cfg.yarn_factor, cfg.yarn_mscale,
            cfg.yarn_mscale_all_dim, cfg.yarn_original_max_pos,
            cfg.yarn_beta_fast, cfg.yarn_beta_slow, cfg.tie_embeddings)
    want = (dims.layers, dims.d, dims.heads, dims.kv_rank, dims.nope,
            dims.rope, dims.v, dims.d_ff, dims.moe_ff, dims.experts,
            dims.top_k, dims.shared, dims.first_dense,
            int(config.get("moe_layer_freq", 1)), dims.vocab, dims.eps,
            dims.theta, dims.norm_topk, dims.scaling, dims.yarn_factor,
            dims.yarn_mscale, dims.yarn_mscale_all, dims.yarn_original,
            dims.yarn_beta_fast, dims.yarn_beta_slow,
            bool(config.get("tie_word_embeddings", False)))
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


def _mlp(k, d: int, f: int, dtype) -> Dict:
    return {"w_gate": _uniform(k[0], (d, f), d, dtype),
            "w_up": _uniform(k[1], (d, f), d, dtype),
            "w_down": _uniform(k[2], (f, d), f, dtype)}


@functools.partial(jax.jit, static_argnums=(2, 3))
def _draw_layer(key, layer, dims: Dims, dense: bool) -> Dict:
    k = jax.random.split(jax.random.fold_in(key, 1 + layer), 16)
    d, h, r = dims.d, dims.heads, dims.kv_rank
    bf = jnp.bfloat16
    p = {
        "norm1": _scale(k[0], (d,), bf),
        "mla": {"wq": _uniform(k[1], (d, h * (dims.nope + dims.rope)), d, bf),
                "wkv_a": _uniform(k[2], (d, r + dims.rope), d, bf),
                "kv_norm": _scale(k[3], (r,), bf),
                "wkv_b": _uniform(k[4], (r, h * (dims.nope + dims.v)), r, bf),
                "wo": _uniform(k[5], (h * dims.v, d), h * dims.v, bf)},
        "norm2": _scale(k[6], (d,), bf),
    }
    if dense:
        p["ffn"] = _mlp(k[7:10], d, dims.d_ff, bf)
        return p
    e, f = dims.experts, dims.moe_ff
    p["moe"] = {"router": _uniform(k[10], (d, e), d, jnp.float32),
                "experts_w_gate": _uniform(k[11], (e, d, f), d, bf),
                "experts_w_up": _uniform(k[12], (e, d, f), d, bf),
                "experts_w_down": _uniform(k[13], (e, f, d), f, bf)}
    if dims.shared:
        p["moe"]["shared"] = _mlp(jax.random.split(k[14], 3), d,
                                  dims.shared * f, bf)
    return p


def draw_layer(key, layer: int, dims: Dims) -> Dict:
    """Layer ``layer``'s weights (bf16; the router in f32, as served)."""
    return _draw_layer(key, layer, dims, layer < dims.first_dense)


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
    lead = [draw_layer(key, li, dims) for li in range(dims.first_dense)]
    layers = [draw_layer(key, li, dims)
              for li in range(dims.first_dense, dims.layers)]
    params = dict(draw_base(key, dims))
    params["lead"] = lead
    params["layers"] = [jax.tree.map(lambda *a: LayerStack(list(a)),
                                     *layers)]
    jax.block_until_ready(jax.tree.leaves((lead, layers)))
    return params


def host_params(seed: int, dims: Dims) -> Dict:
    """Base weights on the device; every layer drawn on the device and
    copied into host memory (numpy), where the program streams it from
    (the leading dense layers too: the program pins them)."""
    key = base_key(seed)
    lead = [jax.device_get(draw_layer(key, li, dims))
            for li in range(dims.first_dense)]
    n = dims.layers - dims.first_dense
    host = None
    for g in range(n):
        layer = jax.device_get(draw_layer(key, dims.first_dense + g, dims))
        if host is None:
            host = jax.tree.map(
                lambda a: np.empty((n,) + a.shape, a.dtype), layer)
        for dst, src in zip(jax.tree.leaves(host), jax.tree.leaves(layer)):
            dst[g] = src
        del layer
    params = dict(draw_base(key, dims))
    params["lead"] = lead
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


def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def inv_freq(dims: Dims) -> np.ndarray:
    """The rope dims' inverse frequencies, yarn-scaled as the published
    ``DeepseekV2YarnRotaryEmbedding`` computes them."""
    dim, base = dims.rope, dims.theta
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not dims.yarn_factor:
        return extra.astype(np.float32)
    inter = extra / dims.yarn_factor

    def corr_dim(rotations):
        return (dim * math.log(dims.yarn_original
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr_dim(dims.yarn_beta_fast)), 0)
    high = min(math.ceil(corr_dim(dims.yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                 # share of the original frequency
    return (inter * (1 - keep) + extra * keep).astype(np.float32)


def softmax_scale(dims: Dims) -> float:
    s = (dims.nope + dims.rope) ** -0.5
    if dims.yarn_factor and dims.yarn_mscale_all:
        m = yarn_mscale(dims.yarn_factor, dims.yarn_mscale_all)
        s *= m * m
    return s


def rope(x, dims: Dims):
    """yarn RoPE over (S, heads, rope) at positions 0..S-1: de-interleave
    (evens, then odds), then rotate half against half."""
    S, H, r = x.shape
    x = x.reshape(S, H, r // 2, 2).swapaxes(-1, -2).reshape(S, H, r)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv_freq(dims))[None, :]
    m = 1.0
    if dims.yarn_factor:
        m = (yarn_mscale(dims.yarn_factor, dims.yarn_mscale)
             / yarn_mscale(dims.yarn_factor, dims.yarn_mscale_all))
    cos = (jnp.cos(ang) * m)[:, None, :]
    sin = (jnp.sin(ang) * m)[:, None, :]
    x1, x2 = x[..., : r // 2], x[..., r // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mla(dims: Dims, p, h, low: bool):
    """Latent attention in the decompressed form over one sequence."""
    S = h.shape[0]
    H, nope, r = dims.heads, dims.nope, dims.kv_rank
    q = mm(h, p["wq"], low).reshape(S, H, nope + dims.rope)
    q_nope, q_pe = q[..., :nope], rope(q[..., nope:], dims)
    ckv = mm(h, p["wkv_a"], low)
    lat = rms_norm(ckv[:, :r], p["kv_norm"], dims.eps)
    k_pe = rope(ckv[:, None, r:], dims)                     # (S, 1, rope)
    kv = mm(lat, p["wkv_b"], low).reshape(S, H, nope + dims.v)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    qh = jnp.concatenate([q_nope, q_pe], -1)
    kh = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (S, H, dims.rope))],
                         -1)
    qh, kh, vh = (jnp.swapaxes(t, 0, 1) for t in (qh, kh, v))  # (H, S, .)
    if low:
        qh, kh, vh = _fp8(qh, -1), _fp8(kh, None), _fp8(vh, None)
    s = jnp.einsum("hqd,hkd->hqk", qh, kh, precision=HI) * softmax_scale(dims)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    pr = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    if low:
        pr = _fp8(pr, -1)
    o = jnp.einsum("hqk,hkd->qhd", pr, vh, precision=HI).reshape(S, H * dims.v)
    return mm(o, p["wo"], low)


def mlp(p, h, low: bool):
    g = mm(h, p["w_gate"], low)
    u = mm(h, p["w_up"], low)
    return mm(jax.nn.silu(g) * u, p["w_down"], low)


def moe(dims: Dims, p, h, low: bool):
    """Softmax router, greedy top-k (not renormalised unless the config
    says so), every routed expert weighted by its gate, plus the shared
    experts."""
    S = h.shape[0]
    probs = jax.nn.softmax(mm(h, p["router"], low), axis=-1)
    top, idx = jax.lax.top_k(probs, dims.top_k)
    if dims.norm_topk:
        top = top / jnp.sum(top, -1, keepdims=True)
    else:
        top = top * dims.scaling
    gate = jnp.zeros_like(probs).at[jnp.arange(S)[:, None], idx].set(top)

    def expert(y, e):
        w = {"w_gate": p["experts_w_gate"][e], "w_up": p["experts_w_up"][e],
             "w_down": p["experts_w_down"][e]}
        return y + jnp.take(gate, e, axis=1)[:, None] * mlp(w, h, low), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(dims.experts))
    if "shared" in p:
        y = y + mlp(p["shared"], h, low)
    return y


@functools.partial(jax.jit, static_argnums=(0, 3))
def layer(dims: Dims, p, x, low: bool):
    """One decoder layer over one sequence x (S, D), float32."""
    x = x + mla(dims, p["mla"], rms_norm(x, p["norm1"], dims.eps), low)
    h = rms_norm(x, p["norm2"], dims.eps)
    if "ffn" in p:
        return x + mlp(p["ffn"], h, low)
    return x + moe(dims, p["moe"], h, low)


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

def ffn_flops(dims: Dims, li: int) -> int:
    """FLOPs of one token through layer ``li``'s FFN: the dense MLP, or
    the router, its ``top_k`` routed experts and the shared experts."""
    if li < dims.first_dense:
        return 2 * 3 * dims.d * dims.d_ff
    router = dims.d * dims.experts
    experts = (dims.top_k + dims.shared) * 3 * dims.d * dims.moe_ff
    return 2 * (router + experts)


def ffn_flops_all(dims: Dims) -> int:
    return sum(ffn_flops(dims, li) for li in range(dims.layers))


def decode_proj_flops(dims: Dims) -> int:
    """FLOPs of one token's latent-attention projections in one layer, in
    the absorbed form: q, the latent and rope key, W_UK into the query,
    W_UV out of the attended latent, and the output projection."""
    h, r = dims.heads, dims.kv_rank
    return 2 * (dims.d * h * (dims.nope + dims.rope)
                + dims.d * (r + dims.rope)
                + h * dims.nope * r + h * r * dims.v
                + h * dims.v * dims.d)


def prefill_proj_flops(dims: Dims) -> int:
    """The same in the decompressed form: the latent up-projected into
    every head's key and value in place of the absorptions."""
    h, r = dims.heads, dims.kv_rank
    return 2 * (dims.d * h * (dims.nope + dims.rope)
                + dims.d * (r + dims.rope)
                + r * h * (dims.nope + dims.v)
                + h * dims.v * dims.d)


def decode_attention_flops(dims: Dims, keys: int) -> int:
    """One query attending ``keys`` cached rows in one layer, absorbed:
    every head scores each row's latent and rope key and sums the
    latents."""
    return 2 * keys * dims.heads * (dims.cache_width + dims.kv_rank)


def prefill_attention_flops(dims: Dims, keys: int) -> int:
    """One query attending ``keys`` keys in one layer, decompressed."""
    return 2 * keys * dims.heads * (dims.nope + dims.rope + dims.v)


def head_flops(dims: Dims) -> int:
    return 2 * dims.d * dims.vocab


def prefill_flops(dims: Dims, n: int) -> int:
    """A prompt of ``n`` tokens (causal: position p attends p + 1 keys),
    and the head on its last token."""
    weights = n * (dims.layers * prefill_proj_flops(dims)
                   + ffn_flops_all(dims))
    attn = dims.layers * prefill_attention_flops(dims, 1) * n * (n + 1) // 2
    return weights + attn + head_flops(dims)


def decode_flops(dims: Dims, pos: int) -> int:
    """One generated token fed at position ``pos`` (attends pos + 1
    cached rows), with its head."""
    per_layer = decode_proj_flops(dims) + decode_attention_flops(dims,
                                                                 pos + 1)
    return dims.layers * per_layer + ffn_flops_all(dims) + head_flops(dims)


def expert_ffn_work(dims: Dims, copies: int, experts_hit: int,
                    itemsize: int = 2):
    """(FLOPs, bytes) one grouped expert FFN call needs for ``copies``
    routed token copies over ``experts_hit`` routed experts: three matmuls
    per copy, each hit expert's three weight matrices read once, each
    copy's row read in and written out.  The shared experts are not in
    the call."""
    flops = copies * 3 * 2 * dims.d * dims.moe_ff
    weights = experts_hit * 3 * dims.d * dims.moe_ff * itemsize
    rows = copies * 2 * dims.d * itemsize
    return flops, weights + rows

