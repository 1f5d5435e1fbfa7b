"""Operations and bytes one ragged serving step of the ``axk1`` family NEEDS,
from its shapes, its rows and what the step's router decided: the family's own
count (``harness/roofline.py`` counts a dense grouped-query llama layer,
``roofline_afmoe.py`` K and V by head).

As there, the count is the algorithm's, for the step's LIVE tokens only, in
the ABSORBED form the program serves (the cheaper one at these chunk sizes):

* ``walk_*``: the attention walks alone, what ``mla_walk_roofline_share`` holds
  their device time against.  Per query slot and visible key a layer costs one
  score over the ``kv_lora_rank + qk_rope_head_dim`` columns of the latent and
  one value sum over its ``kv_lora_rank`` columns for each of the heads: 2 x 64
  x (576 + 512) = 139 kFLOP at the published widths; each row's latent is read
  ONCE a row and layer up to its last fed position (1152 B a position), the new
  positions' latents are written.
* ``step_*``: the whole step: every unrouted matrix once (the low-rank q and kv
  projections, ``Wkvb`` for both absorptions, ``Wo``, the dense layer, router
  and shared expert of each expert layer), each TOUCHED expert's three matrices
  once, the walks, the output head over the vocabulary slice once if any
  position needs logits.

``rows``: ``(tokens fed, start, positions that need logits)`` per sequence, as
``families/axk1.py`` notes them.  Alignment zeros the arena carries beside a
latent (576 -> 640 columns, a TPU tile) are the program's, not the
algorithm's, and are not counted.
"""
from __future__ import annotations

BF16 = 2  # bytes


def latent_dim(doc: dict) -> int:
    return doc["kv_lora_rank"] + doc["qk_rope_head_dim"]


def slot_key_flops(doc: dict) -> float:
    """One query slot against one visible key, one layer, all heads."""
    return 2.0 * doc["num_attention_heads"] * (latent_dim(doc) + doc["kv_lora_rank"])


def seen_positions(n: int, start: int) -> int:
    """Keys the ``n`` tokens of a row fed from ``start`` attend to, summed."""
    return n * start + n * (n + 1) // 2


def walk_flops(doc: dict, rows: list[tuple]) -> float:
    return slot_key_flops(doc) * doc["num_hidden_layers"] * sum(
        seen_positions(n, s) for n, s, _ in rows)


def walk_bytes(doc: dict, rows: list[tuple]) -> float:
    """Each row's latent once a layer up to its last fed position (the fed
    positions' own latents among them, written before the walk reads them)."""
    per = latent_dim(doc) * BF16 * doc["num_hidden_layers"]
    return float(per * sum(s + n for n, s, _ in rows) + per * sum(n for n, _, _ in rows))


def attn_params(doc: dict) -> int:
    """Wqa, Wqb, Wkva, Wkvb and Wo of one layer."""
    d, h = doc["hidden_size"], doc["num_attention_heads"]
    qr, kr = doc["q_lora_rank"], doc["kv_lora_rank"]
    nope, rd, vd = doc["qk_nope_head_dim"], doc["qk_rope_head_dim"], doc["v_head_dim"]
    return d * qr + qr * h * (nope + rd) + d * (kr + rd) + kr * h * (nope + vd) + h * vd * d


def expert_params(doc: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * doc["hidden_size"] * doc["moe_intermediate_size"]


def unrouted_params(doc: dict) -> int:
    """Every matrix a step reads whatever the router says, all layers."""
    d, L, nd = doc["hidden_size"], doc["num_hidden_layers"], doc["first_k_dense_replace"]
    dense = 3 * d * doc["intermediate_size"]
    per_expert_layer = d * doc["num_experts_routed"] + doc["n_shared_experts"] * expert_params(doc)
    return L * attn_params(doc) + nd * dense + (L - nd) * per_expert_layer


def step_flops(doc: dict, rows: list[tuple], assignments_here: int) -> float:
    """Multiply-adds x 2.  Every token passes every unrouted matrix once
    (``Wkvb``'s two halves as the two absorptions: the same count as
    expanding ONE position), its assignments to held experts their experts."""
    tokens = sum(n for n, _, _ in rows)
    head = 2.0 * doc["hidden_size"] * doc["vocab_size"] * sum(hd for _, _, hd in rows)
    return (2.0 * unrouted_params(doc) * tokens + 2.0 * expert_params(doc) * assignments_here
            + walk_flops(doc, rows) + head)


def step_bytes(doc: dict, rows: list[tuple], touched: int) -> float:
    """HBM traffic.  ``touched``: (expert layer, held expert) pairs that got
    at least one token this step."""
    d = doc["hidden_size"]
    tokens = sum(n for n, _, _ in rows)
    weights = (unrouted_params(doc) + touched * expert_params(doc)) * BF16
    head = d * doc["vocab_size"] * BF16 if any(hd for _, _, hd in rows) else 0
    return float(weights + head + tokens * d * BF16 + walk_bytes(doc, rows))


def least(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    by_flops = flops / peaks["bf16_flops"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (by_flops, "flops") if by_flops >= by_bytes else (by_bytes, "bandwidth")


def walk_least_seconds(doc: dict, rows: list[tuple], peaks: dict) -> tuple[float, str]:
    return least(walk_flops(doc, rows), walk_bytes(doc, rows), peaks)


def step_least_seconds(doc: dict, rows: list[tuple], counters: dict, peaks: dict) -> tuple[float, str]:
    """``counters``: the step's expert counters as the program names them
    (``moe_assignments_here``, ``moe_experts_touched``)."""
    return least(step_flops(doc, rows, counters["moe_assignments_here"]),
                 step_bytes(doc, rows, counters["moe_experts_touched"]), peaks)
