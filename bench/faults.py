"""Faults planted in the timed path, to show that the check catches them.

Each takes the built server and breaks the engine's decode call, the one
the window drives, for the rest of the run.  The benchmark's own runs
never plant one; ``tests/bench/test_harness.py`` does at a tiny size, and
``bench/control.py --fault`` at a cell's own size on the chip.
"""
from __future__ import annotations


def altered_token(server) -> None:
    """Every token is altered where it is produced."""
    eng = server._engine
    chunk = eng.decode_chunk

    def altered(*a, **k):
        return (chunk(*a, **k) + 1) % eng.cfg.vocab_size
    eng.decode_chunk = altered


def state_unchanged(server) -> None:
    """Decode returns its cache unchanged: no step writes its keys and
    values."""
    import jax
    import jax.numpy as jnp

    eng = server._engine
    chunk = eng.decode_chunk

    def frozen(*a, **k):
        kept = jax.tree.map(jnp.copy, eng.cache)
        toks = chunk(*a, **k)
        eng.cache = kept
        return toks
    eng.decode_chunk = frozen


def half_the_batch(server) -> None:
    """Only the first half of the batch's rows is decoded; the rest keep
    their last token."""
    import jax.numpy as jnp

    eng = server._engine
    chunk = eng.decode_chunk

    def half(tokens, pos, sampler, T, live=None):
        toks = chunk(tokens, pos, sampler, T, live=live)
        n = toks.shape[0] // 2
        stale = jnp.broadcast_to(jnp.asarray(tokens)[n:, None],
                                 toks[n:].shape)
        return jnp.concatenate([toks[:n], stale.astype(toks.dtype)])
    eng.decode_chunk = half


FAULTS = {f.__name__: f for f in (altered_token, state_unchanged,
                                  half_the_batch)}
