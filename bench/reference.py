"""The plain reference: a Mixtral forward pass in float32, and its control.

Written from the published architecture (arXiv:2401.04088 and the model's
``config.json``), in straightforward ``jax.numpy`` at
``Precision.HIGHEST``: RMSNorm, grouped-query causal attention with
rotate-half RoPE at ``rope_theta``, a softmax router whose top-k gates are
renormalised, SiLU-gated experts, and an untied head.  It imports nothing
of the program and reads none of its state: each layer's weights are drawn
again from the seed (``bench/weights.py``) when the layer runs, then
freed, so the pass fits beside nothing else on the chip.

``judge`` reads served tokens: for every served token, how far its
reference logit lies below the reference's best logit at that position.
With ``control`` it also reads the control at the same positions: the
reference computed in the next precision below the configuration's bf16,
every matmul input rounded to float8 (e4m3, scaled per row and per
tensor) and accumulated in float32.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import Dims, base_key, draw_base, draw_layer

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
