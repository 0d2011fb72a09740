"""Operations and bytes that a step needs, from a configuration's shapes.

These count what the algorithm requires, not what a program computes: a
multiply-add is 2 FLOPs; attention counts ``QK^T`` and ``PV`` over the keys
each query may see (causal: position ``p`` sees ``p + 1`` keys); recomputed
work and masked-out blocks are not counted. Bytes are those a step must move
through HBM at least once: the weights it reads, the cache it reads and the
cache it writes.
"""
from __future__ import annotations


def layer_matmul_params(m) -> int:
    """Weights of one layer that enter a matrix product."""
    d, h, kh, dh, ff = m.d, m.heads, m.kv_heads, m.head_dim, m.ff
    return d * h * dh + 2 * d * kh * dh + h * dh * d + 3 * d * ff


def params(m) -> int:
    """All parameters: embedding, layers with their norms, final norm and
    (untied) output head."""
    per_layer = layer_matmul_params(m) + 2 * m.d + 2 * m.head_dim
    head = 0 if m.tied else m.vocab * m.d
    return m.vocab * m.d + m.layers * per_layer + m.d + head


def attention_flops(m, keys_seen: int) -> int:
    """QK^T and PV of one query over ``keys_seen`` keys, all layers."""
    return m.layers * 4 * m.heads * m.head_dim * keys_seen


def forward_flops(m, tokens: int, keys_seen_total: int, logits_rows: int) -> int:
    """A forward pass over ``tokens`` positions whose queries see
    ``keys_seen_total`` keys in all, with ``logits_rows`` rows of logits."""
    return (2 * tokens * m.layers * layer_matmul_params(m)
            + attention_flops(m, keys_seen_total)
            + 2 * logits_rows * m.d * m.vocab)


def prefill_flops(m, batch: int, length: int) -> int:
    """A causal prefill of ``batch`` prompts of ``length`` tokens that
    returns the last position's logits."""
    return batch * forward_flops(m, length, length * (length + 1) // 2, 1)


def decode_flops(m, batch: int, context: int) -> int:
    """One decode step of ``batch`` sequences whose new token lands at
    position ``context`` (it sees ``context + 1`` keys)."""
    return batch * forward_flops(m, 1, context + 1, 1)


def kv_bytes_per_token(m, itemsize: int = 2) -> int:
    return m.layers * 2 * m.kv_heads * m.head_dim * itemsize


def decode_bytes(m, batch: int, context: int, itemsize: int = 2) -> int:
    """HBM bytes one decode step must move: every weight it reads (the whole
    output matrix, but only ``batch`` rows of an untied embedding), the
    cache's ``context`` positions read once, and one position written."""
    weights = params(m) - (0 if m.tied else (m.vocab - batch) * m.d)
    cache = batch * (context + 1) * kv_bytes_per_token(m, itemsize)
    return weights * itemsize + cache
