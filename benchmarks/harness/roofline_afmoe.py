"""Operations and bytes one ragged serving step of the ``afmoe`` family NEEDS,
from its shapes and from what the step's router decided: the family's own
count (``harness/roofline.py`` counts a dense llama layer).

As there, the count is the algorithm's, for the step's LIVE tokens only: every
non-routed matrix read once (attention with its gate, the dense layer, router
and shared expert of each expert layer), each TOUCHED expert's three matrices
once (an expert held here that no token of the step selected need not be
read), each sequence's cached K and V read once — a window layer's up to the
window, a full layer's up to the row — the new K and V written, the output
head over the vocabulary slice once if any position needs logits.

``experts_*`` count the expert products alone (the three grouped products of
every expert layer): what ``moe_experts_roofline_share`` holds their device
time against.
"""
from __future__ import annotations

BF16 = 2  # bytes
KINDS = ("sliding_attention", "full_attention")


def attn_params(doc: dict) -> int:
    """wq, wk, wv, the gate and wo of one layer."""
    d = doc["hidden_size"]
    q = doc["num_attention_heads"] * doc["head_dim"]
    kv = doc["num_key_value_heads"] * doc["head_dim"]
    return 2 * d * q + 2 * d * kv + q * d


def expert_params(doc: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * doc["hidden_size"] * doc["moe_intermediate_size"]


def unrouted_params(doc: dict) -> int:
    """Every matrix a step reads whatever the router says, all layers."""
    d, L, nd = doc["hidden_size"], doc["num_hidden_layers"], doc["num_dense_layers"]
    dense = 3 * d * doc["intermediate_size"]
    per_expert_layer = d * doc["num_experts_routed"] + doc["num_shared_experts"] * expert_params(doc)
    return L * attn_params(doc) + nd * dense + (L - nd) * per_expert_layer


def seen_positions(doc: dict, n: int, start: int, kind: str) -> int:
    """Keys the ``n`` tokens of a row fed from ``start`` attend to, summed."""
    w = doc["sliding_window"]
    total = 0
    for p in range(start, start + n):
        total += min(p + 1, w) if kind == KINDS[0] else p + 1
    return total


def cached_positions(doc: dict, n: int, start: int, kind: str) -> int:
    """Positions of K (and of V) the row must read: its whole cached row in a
    full layer, the union of its tokens' windows in a window layer."""
    end = start + n
    if kind == KINDS[1]:
        return end
    return end - max(0, start - doc["sliding_window"] + 1)


def step_flops(doc: dict, rows: list[tuple], assignments_here: int) -> float:
    """Multiply-adds x 2.  ``rows``: (tokens fed, start, positions that need
    logits) per sequence; ``assignments_here``: token-expert assignments to
    experts held here, over all expert layers."""
    d, h, hd = doc["hidden_size"], doc["num_attention_heads"], doc["head_dim"]
    tokens = sum(n for n, _, _ in rows)
    flops = 2.0 * unrouted_params(doc) * tokens + experts_flops(doc, assignments_here)
    for kind in doc["layer_types"]:
        flops += 2.0 * 2.0 * h * hd * sum(seen_positions(doc, n, s, kind) for n, s, _ in rows)
    return flops + 2.0 * d * doc["vocab_size"] * sum(hd_ for _, _, hd_ in rows)


def step_bytes(doc: dict, rows: list[tuple], touched: int) -> float:
    """HBM traffic.  ``touched``: (expert layer, held expert) pairs that got
    at least one token this step."""
    d, L = doc["hidden_size"], doc["num_hidden_layers"]
    kvw = doc["num_key_value_heads"] * doc["head_dim"]
    tokens = sum(n for n, _, _ in rows)
    weights = (unrouted_params(doc) + touched * expert_params(doc)) * BF16
    head = d * doc["vocab_size"] * BF16 if any(hd_ for _, _, hd_ in rows) else 0
    embed = tokens * d * BF16
    kv_read = sum(cached_positions(doc, n, s, kind) for kind in doc["layer_types"]
                  for n, s, _ in rows) * 2 * kvw * BF16
    kv_write = tokens * 2 * kvw * L * BF16
    return float(weights + head + embed + kv_read + kv_write)


def experts_flops(doc: dict, assignments_here: int) -> float:
    return 2.0 * expert_params(doc) * assignments_here


def experts_bytes(doc: dict, assignments_here: int, touched: int) -> float:
    """The touched experts' matrices once; each assignment's input row, its
    two hidden rows written and read back, its output row (float32)."""
    d, fe = doc["hidden_size"], doc["moe_intermediate_size"]
    rows = assignments_here * (2 * d * BF16 + 3 * fe * BF16 + d * 4)
    return float(touched * expert_params(doc) * BF16 + rows)


def least(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    by_flops = flops / peaks["bf16_flops"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (by_flops, "flops") if by_flops >= by_bytes else (by_bytes, "bandwidth")


def step_least_seconds(doc: dict, rows: list[tuple], counters: dict, peaks: dict) -> tuple[float, str]:
    """``counters``: the step's expert counters as the program names them
    (``moe_assignments_here``, ``moe_experts_touched``)."""
    here, touched = counters["moe_assignments_here"], counters["moe_experts_touched"]
    return least(step_flops(doc, rows, here), step_bytes(doc, rows, touched), peaks)


def experts_least_seconds(doc: dict, counters: dict, peaks: dict) -> tuple[float, str]:
    here, touched = counters["moe_assignments_here"], counters["moe_experts_touched"]
    return least(experts_flops(doc, here), experts_bytes(doc, here, touched), peaks)
