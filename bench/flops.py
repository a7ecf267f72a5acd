"""Operations and bytes the work needs, from the model's shapes alone.

Counts are of the work the tokens need: the matmuls of the projections,
of the router and of the ``top_k`` experts each token is routed to, the
attention over the keys a token attends, and the head where a token's
logits are produced.  Padding, capacity rows and experts a token is not
routed to never enter, so a kernel or a step is judged against the same
work however the program lays it out.
"""
from __future__ import annotations

from bench.weights import Dims


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
