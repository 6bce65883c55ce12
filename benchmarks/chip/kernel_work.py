"""The work a call needs, from its shapes: what a kernel or a step must
compute and move whatever implements it.

Counts are of the algorithm, not of an implementation: padded batch
lanes, masked page-table entries, the pool's relayout and recomputed
values are not work.  A multiply-add is two operations.
"""

from __future__ import annotations

from typing import Dict, Sequence

#: bytes of one element as served (bfloat16 weights, KV and queries)
BF16 = 2


def paged_attention(lens: Sequence[int], heads: int, kv_heads: int,
                    head_dim: int, *, kv_bytes: int = BF16,
                    q_bytes: int = BF16):
    """(operations, bytes) of one paged-attention call (one layer of one
    decode step) over the real lanes whose valid lengths are ``lens``:
    each lane's query against its live keys and values (scores and the
    weighted sum, ``4 * len * heads * head_dim``), reading those keys and
    values once and the query and output once."""
    live = sum(int(n) for n in lens)
    flops = 4 * live * heads * head_dim
    byts = (live * kv_heads * head_dim * 2 * kv_bytes
            + len(lens) * heads * head_dim * 2 * q_bytes)
    return flops, byts


def layer_matmul_flops(m: Dict) -> int:
    """Operations of one token through one layer's weights: q, k, v and
    output projections and the gated MLP's three matrices."""
    d, h, kv, hd, ff = (m["d_model"], m["heads"], m["kv_heads"],
                        m["head_dim"], m["d_ff"])
    return 2 * d * (2 * h * hd + 2 * kv * hd) + 2 * 3 * d * ff


def head_flops(m: Dict) -> int:
    """Operations of one row of logits."""
    return 2 * m["d_model"] * m["vocab"]


def decode_flops(m: Dict, lens: Sequence[int]) -> int:
    """Operations of one decode step over the real lanes with valid
    lengths ``lens``: each lane's token through every layer, its
    attention over its live context, and its row of logits."""
    per_layer = sum(layer_matmul_flops(m) + 4 * int(n) * m["heads"]
                    * m["head_dim"] for n in lens)
    return per_layer * m["layers"] + len(lens) * head_flops(m)


def prefill_flops(m: Dict, cached: int, total: int) -> int:
    """Operations of prefilling prompt positions ``cached .. total - 1``
    (the cached ones are not computed): each through every layer,
    attending causally over all earlier positions, and one row of logits
    for the last."""
    n = total - cached
    if n <= 0:
        return 0
    # sum over positions t of (t + 1) keys
    keys = (total * (total + 1) - cached * (cached + 1)) // 2
    per_layer = n * layer_matmul_flops(m) + 4 * keys * m["heads"] * m[
        "head_dim"]
    return per_layer * m["layers"] + head_flops(m)
