"""Operations and HBM bytes of the flash attention kernel, from its shapes.

One call attends ``B * H`` query heads of ``Sq`` rows onto ``B * KVH`` key
and value heads of ``Skv`` rows (grouped-query: ``H / KVH`` query heads
share one), head size ``d`` for queries and keys and ``dv`` for values.
The work is what the algorithm needs, not what the kernel happens to do:
the (query, key) pairs the mask keeps (causal: key j <= query i; a window
w: j > i - w), not the blocks it computes, and each tensor read or written once.

- forward: scores ``2 d`` and the weighted values ``2 dv`` per pair; reads
  q, k, v, writes the output and one f32 log-sum-exp per query row;
- backward: the scores again ``2 d``, dV ``2 dv``, dP ``2 dv``, dQ ``2 d``
  and dK ``2 d`` per pair; reads q, k, v, the output, its cotangent and
  the log-sum-exp, writes dq, dk, dv.

Elements of q, the output and their cotangents take ``itemsize`` bytes,
those of k and v ``kv_itemsize`` (the same unless given); the log-sum-exp
is f32.  Softmax exponentials and the mask are not counted.
"""

from __future__ import annotations


def pairs(sq: int, skv: int, causal: bool, window: int = 0) -> int:
    """(query, key) pairs one head attends: key j for query i where
    j <= i (causal) and j > i - window (a sliding window)."""
    total = 0
    for i in range(sq):
        hi = min(i, skv - 1) if causal else skv - 1
        lo = max(0, i - window + 1) if window else 0
        total += max(0, hi - lo + 1)
    return total


def forward_flops(B, H, KVH, Sq, Skv, d, causal, dv=None,
                  window=0) -> float:
    dv = d if dv is None else dv
    return float(B * H * pairs(Sq, Skv, causal, window) * 2 * (d + dv))


def forward_bytes(B, H, KVH, Sq, Skv, d, causal, dv=None, itemsize=2,
                  kv_itemsize=None) -> float:
    dv = d if dv is None else dv
    kv_itemsize = itemsize if kv_itemsize is None else kv_itemsize
    q, o = B * H * Sq * d, B * H * Sq * dv
    kv = B * KVH * Skv * (d + dv)
    return float((q + o) * itemsize + kv * kv_itemsize + B * H * Sq * 4)


def backward_flops(B, H, KVH, Sq, Skv, d, causal, dv=None,
                   window=0) -> float:
    dv = d if dv is None else dv
    return float(B * H * pairs(Sq, Skv, causal, window) * 2
                 * (3 * d + 2 * dv))


def backward_bytes(B, H, KVH, Sq, Skv, d, causal, dv=None, itemsize=2,
                   kv_itemsize=None) -> float:
    dv = d if dv is None else dv
    kv_itemsize = itemsize if kv_itemsize is None else kv_itemsize
    q, o = B * H * Sq * d, B * H * Sq * dv
    kv = B * KVH * Skv * (d + dv)
    return float((2 * q + 2 * o) * itemsize + 2 * kv * kv_itemsize
                 + B * H * Sq * 4)
