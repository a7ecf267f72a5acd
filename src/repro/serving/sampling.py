"""Token sampling: per-request params, batched per-slot device-side sampling.

The engine's default decoding strategy is greedy argmax (paper §B); online
serving needs per-request sampling — a batch may mix greedy slots with
seeded temperature / top-k slots.  ``SamplingParams`` is the per-request
policy, ``BatchSampler`` holds one slot of sampling state per engine batch
row and turns a ``(B, V)`` logits array into ``(B,)`` next tokens in a
single jitted launch (``_sample_module``): per-slot Gumbel-max over
temperature-scaled, top-k-masked logits, greedy slots taking the plain
argmax.

Determinism contract: slot *i*'s token at its *t*-th generated position is
a pure function of ``(logits, PRNGKey(seed), t)`` — the key is folded with
the per-request token index, not any global step counter, so the same
request produces the same stream under the static and the continuous
scheduler, across runs, and regardless of which batch slot it lands in.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import runtime as sanitizer
from repro.analysis.registry import register_jit
from repro.analysis.spans import span


@dataclass(frozen=True)
class SamplingParams:
    """Per-request decoding policy.

    ``temperature <= 0`` means greedy (argmax) — identical to the engine's
    default.  ``top_k > 0`` restricts sampling to the k highest logits.
    ``seed`` determines the request's whole token stream (see the module
    determinism contract).
    """

    temperature: float = 0.0
    top_k: int = 0
    seed: int = 0

    @property
    def is_greedy(self) -> bool:
        return self.temperature <= 0.0


GREEDY = SamplingParams()


def greedy(logits: jax.Array) -> jax.Array:
    return jnp.argmax(logits, axis=-1)


def temperature_sample(key, logits: jax.Array, temperature: float = 1.0):
    if temperature <= 0:
        return greedy(logits)
    return jax.random.categorical(key, logits / temperature, axis=-1)


def top_k_sample(key, logits: jax.Array, k: int, temperature: float = 1.0):
    vals, idx = jax.lax.top_k(logits, k)
    choice = jax.random.categorical(key, vals / max(temperature, 1e-6), axis=-1)
    return jnp.take_along_axis(idx, choice[..., None], axis=-1)[..., 0]


def sample_tokens(logits, keys, steps, temps, topks, use_topk):
    """Traceable batched sampling math: (B, V) logits -> (B,) tokens.

    This is THE per-slot sampling function — ``BatchSampler`` launches it as
    its own jitted module (``_sample_module``) and the engine's fused decode
    macro-step inlines it inside the one-launch chunk, so both paths share
    bit-identical sampling (the fused/per-module token-identity contract
    depends on this being the single implementation).

    Per-slot Gumbel-max categorical over temperature-scaled logits with an
    optional top-k mask; slots with ``temps <= 0`` take the greedy argmax
    (on the raw logits, so a greedy slot is bit-identical to
    ``jnp.argmax``).  ``keys`` are per-slot base PRNG keys folded with
    ``steps`` (the slot's token index), which is what makes a request's
    stream independent of scheduler, slot and batch composition.
    ``use_topk=False`` (static, set by the caller when no selected slot has
    ``top_k > 0``) skips the O(B*V log V) vocab sort the kth-threshold
    needs — pure-temperature slots sample identically either way, since
    their ``(k > 0)`` mask discards the threshold.
    """
    B, V = logits.shape
    greedy_tok = jnp.argmax(logits, axis=-1)
    lg = logits.astype(jnp.float32)
    if use_topk:
        k = jnp.clip(topks, 0, V)
        sorted_desc = -jnp.sort(-lg, axis=-1)
        kth = jnp.take_along_axis(
            sorted_desc, (jnp.maximum(k, 1) - 1)[:, None], axis=-1
        )                                                   # (B, 1)
        lg = jnp.where((k[:, None] > 0) & (lg < kth), -jnp.inf, lg)
    scaled = lg / jnp.maximum(temps, 1e-6)[:, None]

    def noise(key, step):
        return jax.random.gumbel(jax.random.fold_in(key, step), (V,),
                                 jnp.float32)

    gum = jax.vmap(noise)(keys, steps)
    sampled = jnp.argmax(scaled + gum, axis=-1)
    return jnp.where(temps > 0, sampled, greedy_tok)


_sample_module = register_jit("sampling.sample")(
    functools.partial(jax.jit, static_argnames=("use_topk",))(sample_tokens)
)


class BatchSampler:
    """Per-slot sampling state for one engine batch.

    The scheduler sets a slot's ``SamplingParams`` at admission
    (``set_slot``), clears it at eviction (``clear_slot``; cleared slots
    are greedy no-ops), and calls ``sample`` once per logits column —
    each call advances the sampled slots' token indices by one.  When
    every selected slot is greedy the call is a plain ``jnp.argmax`` (no
    keys materialized, no extra launch).
    """

    def __init__(self, nslots: int) -> None:
        self.nslots = nslots
        self._keys = np.zeros((nslots, 2), np.uint32)
        self._steps = np.zeros(nslots, np.int32)
        self._temps = np.zeros(nslots, np.float32)
        self._topks = np.zeros(nslots, np.int32)

    def set_slot(self, i: int, params: Optional[SamplingParams],
                 salt: Optional[int] = None) -> None:
        """Arm slot ``i`` with ``params`` (None = greedy), resetting its
        token index.  ``salt`` (when given) is folded into the base key —
        used by uniform batch APIs to decorrelate rows sharing one seed."""
        sp = params or GREEDY
        key = jax.random.PRNGKey(sp.seed)
        if salt is not None:
            key = jax.random.fold_in(key, salt)
        self._keys[i] = np.asarray(key, np.uint32)
        self._steps[i] = 0
        self._temps[i] = max(0.0, float(sp.temperature))
        self._topks[i] = int(sp.top_k)

    def clear_slot(self, i: int) -> None:
        self._keys[i] = 0
        self._steps[i] = 0
        self._temps[i] = 0.0
        self._topks[i] = 0

    @classmethod
    def uniform(cls, nslots: int,
                params: Optional[SamplingParams]) -> "BatchSampler":
        """One shared policy for every slot, with the row index folded into
        each slot's key so rows sharing a seed draw independent streams."""
        s = cls(nslots)
        if params is not None:
            for i in range(nslots):
                s.set_slot(i, params, salt=i)
        return s

    def state(self, slots: Sequence[int]):
        """The selected slots' raw sampling state ``(keys, steps, temps,
        topks)`` — consumed by the engine's fused decode chunk, which inlines
        ``sample_tokens`` on device and advances the slots with
        ``advance()`` afterwards."""
        idx = np.asarray(slots, np.int64)
        return (self._keys[idx].copy(), self._steps[idx].copy(),
                self._temps[idx].copy(), self._topks[idx].copy())

    def advance(self, slots: Sequence[int], n: int = 1) -> None:
        """Advance the selected slots' token indices by ``n`` (the fused
        chunk sampled ``n`` tokens per slot device-side)."""
        self._steps[np.asarray(slots, np.int64)] += n

    def sample(self, logits: jax.Array,
               slots: Optional[Sequence[int]] = None) -> jax.Array:
        """Next token for each selected slot: (n, V) logits -> (n,) tokens,
        row j of ``logits`` belonging to ``slots[j]`` (default: all)."""
        with span("sample"):
            idx = (np.arange(self.nslots) if slots is None
                   else np.asarray(slots, np.int64))
            assert logits.shape[0] == idx.size, (logits.shape, idx.size)
            if not (self._temps[idx] > 0).any():
                self._steps[idx] += 1
                return jnp.argmax(logits, axis=-1)
            with sanitizer.allowed("sampler-state"):
                toks = _sample_module(
                    logits,
                    jnp.asarray(self._keys[idx]),
                    jnp.asarray(self._steps[idx]),
                    jnp.asarray(self._temps[idx]),
                    jnp.asarray(self._topks[idx]),
                    use_topk=bool((self._topks[idx] > 0).any()),
                )
            self._steps[idx] += 1
            return toks
