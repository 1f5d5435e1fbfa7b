"""Operations and bytes one ragged serving step of the ``mellum`` family NEEDS,
from its shapes and from what the step's router decided: the family's own
count (``harness/roofline.py`` counts a dense llama layer, ``roofline_afmoe``
a gated attention, a dense layer and a shared expert this family lacks).

As there, the count is the algorithm's, for the step's LIVE tokens only: every
non-routed matrix read once (wq, wk, wv, wo and the router of each layer),
each TOUCHED expert's three matrices once (an expert no token of the step
selected need not be read), each sequence's cached K and V read once — a
window layer's up to the window, a full layer's up to the row — the new K and
V written, the output head over the whole vocabulary once if any position
needs logits.  What the two families count alike (an expert's parameters, the
positions a row sees and reads, the grouped products alone, the larger of the
two least times) is ``roofline_afmoe``'s, imported.
"""
from __future__ import annotations

from .roofline_afmoe import (BF16, cached_positions, expert_params, experts_flops, least,
                             seen_positions)


def attn_params(doc: dict) -> int:
    """wq, wk, wv and wo of one layer (no gate)."""
    d = doc["hidden_size"]
    q = doc["num_attention_heads"] * doc["head_dim"]
    kv = doc["num_key_value_heads"] * doc["head_dim"]
    return d * q + 2 * d * kv + q * d


def unrouted_params(doc: dict) -> int:
    """Every matrix a step reads whatever the router says, all layers:
    attention and the router (no dense layer, no shared expert)."""
    return doc["num_hidden_layers"] * (attn_params(doc) + doc["hidden_size"] * doc["num_experts"])


def step_flops(doc: dict, rows: list[tuple], assignments_here: int) -> float:
    """Multiply-adds x 2.  ``rows``: (tokens fed, start, positions that need
    logits) per sequence; ``assignments_here``: token-expert assignments to
    experts held here (all of them: the set is whole), over all layers."""
    d, h, hd = doc["hidden_size"], doc["num_attention_heads"], doc["head_dim"]
    tokens = sum(n for n, _, _ in rows)
    flops = 2.0 * unrouted_params(doc) * tokens + experts_flops(doc, assignments_here)
    for kind in doc["layer_types"]:
        flops += 2.0 * 2.0 * h * hd * sum(seen_positions(doc, n, s, kind) for n, s, _ in rows)
    return flops + 2.0 * d * doc["vocab_size"] * sum(hd_ for _, _, hd_ in rows)


def step_bytes(doc: dict, rows: list[tuple], touched: int) -> float:
    """HBM traffic.  ``touched``: (layer, expert) pairs that got at least one
    token this step."""
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


def step_least_seconds(doc: dict, rows: list[tuple], counters: dict, peaks: dict) -> tuple[float, str]:
    """``counters``: the step's expert counters as the program names them
    (``moe_assignments_here``, ``moe_experts_touched``)."""
    here, touched = counters["moe_assignments_here"], counters["moe_experts_touched"]
    return least(step_flops(doc, rows, here), step_bytes(doc, rows, touched), peaks)
