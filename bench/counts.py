"""Operations and bytes the model's work needs, from its shapes.

Counts are of the published model: 24 query heads, not the program's
padded 32, and attention over the causal half only, so that work a kernel
wastes shows as a lower share of the peak.  Matrix products count 2
operations per multiply-add; bf16 operands count 2 bytes.
"""

from __future__ import annotations

BYTES = 2


def dims(c: dict) -> tuple[int, int, int, int, int, int, int]:
    d = c["hidden_size"]
    h = c["num_attention_heads"]
    return (c["num_hidden_layers"], d, h, c["num_key_value_heads"], d // h,
            c["intermediate_size"], c["vocab_size"])


def matmul_params(c: dict) -> int:
    """Weights every token multiplies through: the layers and the head
    (the embedding lookup is a gather, not a product)."""
    L, d, h, k, hd, f, v = dims(c)
    per_layer = d * h * hd + 2 * d * k * hd + h * hd * d + 3 * d * f
    return L * per_layer + v * d


def attn_flops(c: dict, keys: int) -> int:
    """Scores and weighted values of one query against ``keys`` keys,
    over every layer."""
    L, _, h, _, hd, _, _ = dims(c)
    return 4 * keys * h * hd * L


def serve_token_flops(c: dict, pos: int) -> int:
    """One token at position ``pos`` through the model (forward)."""
    return 2 * matmul_params(c) + attn_flops(c, pos + 1)


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def train_step_flops(c: dict, batch: int, seq: int) -> int:
    """Forward and backward of one step, without recomputation:
    6 N per token plus three times the causal attention."""
    L, _, h, _, hd, _, _ = dims(c)
    attn = 4 * h * hd * causal_pairs(seq) * L
    return batch * (6 * matmul_params(c) * seq + 3 * attn)


def flash_call(c: dict, batch: int, seq: int) -> tuple[int, int]:
    """(operations, bytes) of one causal flash-attention forward call of
    one layer: q, k, v read and o written once."""
    _, _, h, k, hd, _, _ = dims(c)
    flops = 4 * h * hd * causal_pairs(seq) * batch
    nbytes = batch * seq * (2 * h * hd + 2 * k * hd) * BYTES
    return flops, nbytes


def paged_step_bytes(c: dict, contexts: list[int]) -> int:
    """Bytes one decode step of paged attention needs over every layer:
    each active slot's live keys and values, its query and its output."""
    L, _, h, k, hd, _, _ = dims(c)
    kv = sum(contexts) * 2 * k * hd * BYTES
    qo = len(contexts) * 2 * h * hd * BYTES
    return L * (kv + qo)
