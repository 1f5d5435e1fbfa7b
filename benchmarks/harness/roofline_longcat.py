"""Operations and bytes one ragged serving step of the ``longcat`` family
NEEDS, from its shapes, its rows and what the step's router decided: the
family's own count (``roofline_mla.py`` knows one attention a layer and
multiplies by ``num_hidden_layers``; this block has two latent-attention
sublayers, two dense FFNs and one expert branch a layer).

As there, the count is the algorithm's, for the step's LIVE tokens only, in
the ABSORBED form the program serves:

* every unrouted matrix once: BOTH sublayers' ``Wqa``, ``Wqb``, ``Wkva``,
  ``Wkvb`` (its two halves as the two absorptions: the same count as expanding
  ONE position) and ``Wo``, BOTH dense FFNs and the router (its whole width,
  identity experts included) once a layer;
* each TOUCHED real expert's three matrices once, each assignment to a held
  expert its expert's products;
* a pick of an identity expert as ONE multiply-add a number of the hidden
  size (``w x m``, summed a token), and no byte: it reads no weight;
* the walks: a query slot against a visible key costs what
  ``roofline_mla.slot_key_flops`` says, once a SUBLAYER; each row's latent is
  read once a row and SUBLAYER up to its last fed position, the new
  positions' latents are written;
* the output head over the vocabulary slice once if any position needs logits.

``rows``: ``(tokens fed, start, positions that need logits)`` per sequence, as
the family's tap notes them.  Alignment zeros the arena carries beside a
latent (576 -> 640 columns) are the program's, not the algorithm's.
"""
from __future__ import annotations

from benchmarks.harness.roofline_mla import (BF16, attn_params, latent_dim, least,
                                             seen_positions, slot_key_flops)

SUBLAYERS = 2  # latent-attention sublayers, each with its dense FFN, in one layer


def sublayers(doc: dict) -> int:
    return SUBLAYERS * doc["num_layers"]


def walk_flops(doc: dict, rows: list[tuple]) -> float:
    return slot_key_flops(doc) * sublayers(doc) * sum(seen_positions(n, s) for n, s, _ in rows)


def walk_bytes(doc: dict, rows: list[tuple]) -> float:
    """Each row's latent once a sublayer up to its last fed position (the fed
    positions' own latents among them, written before the walk reads them)."""
    per = latent_dim(doc) * BF16 * sublayers(doc)
    return float(per * sum(s + n for n, s, _ in rows) + per * sum(n for n, _, _ in rows))


def expert_params(doc: dict) -> int:
    """One real expert: gate, up and down."""
    return 3 * doc["hidden_size"] * doc["expert_ffn_hidden_size"]


def unrouted_params(doc: dict) -> int:
    """Every matrix a step reads whatever the router says, all layers."""
    d = doc["hidden_size"]
    sub = attn_params(doc) + 3 * d * doc["ffn_hidden_size"]
    return doc["num_layers"] * (SUBLAYERS * sub + d * doc["num_experts_routed"])


def step_flops(doc: dict, rows: list[tuple], assignments_here: int, zero_picks: int) -> float:
    """Multiply-adds x 2."""
    d = doc["hidden_size"]
    tokens = sum(n for n, _, _ in rows)
    head = 2.0 * d * doc["vocab_size"] * sum(hd for _, _, hd in rows)
    return (2.0 * unrouted_params(doc) * tokens + 2.0 * expert_params(doc) * assignments_here
            + 2.0 * d * zero_picks + walk_flops(doc, rows) + head)


def step_bytes(doc: dict, rows: list[tuple], touched: int) -> float:
    """HBM traffic.  ``touched``: (layer, held expert) pairs that got at
    least one token this step."""
    d = doc["hidden_size"]
    tokens = sum(n for n, _, _ in rows)
    weights = (unrouted_params(doc) + touched * expert_params(doc)) * BF16
    head = d * doc["vocab_size"] * BF16 if any(hd for _, _, hd in rows) else 0
    return float(weights + head + tokens * d * BF16 + walk_bytes(doc, rows))


def step_least_seconds(doc: dict, rows: list[tuple], counters: dict, peaks: dict) -> tuple[float, str]:
    """``counters``: the step's expert counters as the program names them
    (``moe_assignments_here``, ``moe_experts_touched``, ``moe_zero_assignments``)."""
    return least(step_flops(doc, rows, counters["moe_assignments_here"],
                            counters["moe_zero_assignments"]),
                 step_bytes(doc, rows, counters["moe_experts_touched"]), peaks)
