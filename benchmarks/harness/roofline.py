"""Operations and bytes one ragged serving step NEEDS, from its shapes.

The count is the algorithm's, not the program's: the program today gathers a
whole page-table row for every buffer slot and runs padded slots too; what
is counted here is the work for the step's LIVE tokens only — weights read
once, each sequence's cached K/V read once up to its position, the new K/V
written, and the output head only at positions that are sampled or
verified.  So the share reads low exactly as far as the program does more
than it must.
"""
from __future__ import annotations

from dataclasses import dataclass

BF16 = 2  # bytes


@dataclass(frozen=True)
class Row:
    """One sequence's part of a step: ``n`` tokens fed starting at sequence
    position ``start``; ``head`` positions need logits."""
    n: int
    start: int
    head: int


def layer_matmul_params(doc: dict) -> int:
    d, f = doc["hidden_size"], doc["intermediate_size"]
    q = doc["num_attention_heads"] * doc["head_dim"]
    kv = doc["num_key_value_heads"] * doc["head_dim"]
    return d * q + 2 * d * kv + q * d + 3 * d * f


def step_flops(doc: dict, rows: list[Row]) -> float:
    """Multiply-adds x 2: the layer matrices per token, attention scores and
    values per token over its own causal context, the head per head position."""
    L, d, v = doc["num_hidden_layers"], doc["hidden_size"], doc["vocab_size"]
    h, hd = doc["num_attention_heads"], doc["head_dim"]
    tokens = sum(r.n for r in rows)
    matmul = 2.0 * layer_matmul_params(doc) * L * tokens
    # token j of a row attends to start + j + 1 positions: QK^T and PV
    ctx = sum(r.n * r.start + r.n * (r.n + 1) // 2 for r in rows)
    attn = 2.0 * 2.0 * h * hd * ctx * L
    head = 2.0 * d * v * sum(r.head for r in rows)
    return matmul + attn + head


def step_bytes(doc: dict, rows: list[Row]) -> float:
    """HBM traffic: every layer matrix and norm once; the head matrix once if
    any position needs logits; embedding rows; each sequence's cached K and
    V read once up to its last fed position; the new K and V written."""
    L, d, v = doc["num_hidden_layers"], doc["hidden_size"], doc["vocab_size"]
    kvw = doc["num_key_value_heads"] * doc["head_dim"]
    tokens = sum(r.n for r in rows)
    weights = (layer_matmul_params(doc) + 2 * d) * L * BF16 + d * BF16
    head = d * v * BF16 if any(r.head for r in rows) else 0
    embed = tokens * d * BF16
    kv_read = sum(r.start + r.n for r in rows) * 2 * kvw * L * BF16
    kv_write = tokens * 2 * kvw * L * BF16
    return float(weights + head + embed + kv_read + kv_write)


def least_seconds(doc: dict, rows: list[Row], peaks: dict) -> tuple[float, str]:
    """The least time the chip could take for this step, and which peak
    binds: the larger of operations / peak FLOP/s and bytes / peak bytes/s."""
    by_flops = step_flops(doc, rows) / peaks["bf16_flops"]
    by_bytes = step_bytes(doc, rows) / peaks["hbm_bytes_per_s"]
    return (by_flops, "flops") if by_flops >= by_bytes else (by_bytes, "bandwidth")
