"""Operations and bytes one ragged serving step of the ``bailing`` family
NEEDS, from its shapes, its rows and what the step's router decided: the
family's own count (``roofline_mla.py`` knows one latent attention in every
layer; here six of seven attention layers are Kimi Delta Attention, whose
cache is a STATE a row and not positions).

As there, the count is the algorithm's, for the step's LIVE tokens only:

* every unrouted matrix once: a KDA layer's ``Wqkv``, ``Wa``, ``Wb``, ``Wg``,
  ``Wo`` and its convolution's taps, the latent layer's ``Wq``, ``Wkva``,
  ``Wkvb`` (its two halves as the two absorptions), ``Wg`` and ``Wo``, the
  dense FFN, and each expert layer's router and shared expert;
* each TOUCHED held expert's three matrices once, each assignment to a held
  expert its expert's products;
* **the recurrence, from the step's ROWS and not from the form that computes
  them**: each fed row's state (``heads x d_k x d_v`` float32) read once and
  written once a KDA layer whatever the row feeds, its convolution tail
  likewise, a token's five operands read and its output written, and ``7 x
  d_k x d_v`` operations a token and head (the decay, ``S^T k``, the rank-one
  update, ``S^T q``).  A chunk form on the MXU changes none of this;
* the latent layers' walks in absorbed form (``roofline_mla.slot_key_flops``
  a query slot and visible key; a row's latent once a latent layer);
* the output head over the vocabulary slice once if any position needs logits.

``rows``: ``(tokens fed, start, positions that need logits)`` per sequence, as
the family's tap notes them.  The kinds of the kept layers follow from their
published indices (``kept_layers``, ``layer_group_size``,
``first_k_dense_replace``), as in the family's builder and reference.
"""
from __future__ import annotations

from benchmarks.harness.roofline_mla import BF16, latent_dim, least, seen_positions, slot_key_flops

F32 = 4  # bytes: the state and the recurrence's operands


def kinds(doc: dict) -> list[tuple[str, bool]]:
    return [("mla" if (i + 1) % doc["layer_group_size"] == 0 else "kda",
             i < doc["first_k_dense_replace"]) for i in doc["kept_layers"]]


def n_kda(doc: dict) -> int:
    return sum(1 for kind, _ in kinds(doc) if kind == "kda")


def n_mla(doc: dict) -> int:
    return sum(1 for kind, _ in kinds(doc) if kind == "mla")


def kda_params(doc: dict) -> int:
    """One KDA layer's matrices, taps and decay parameters."""
    d, c = doc["hidden_size"], doc["num_attention_heads"] * doc["head_dim"]
    h = doc["num_attention_heads"]
    return (d * 3 * c + doc["short_conv_kernel_size"] * 3 * c + d * c + h + c + 2 * d * h
            + doc["head_dim"] + c * d)


def mla_params(doc: dict) -> int:
    """The latent layer's Wq (direct), Wkva, Wkvb, Wg and Wo."""
    d, h, kr = doc["hidden_size"], doc["num_attention_heads"], doc["kv_lora_rank"]
    nope, rd, vd = doc["qk_nope_head_dim"], doc["qk_rope_head_dim"], doc["v_head_dim"]
    return d * h * (nope + rd) + d * (kr + rd) + kr * h * (nope + vd) + d * h + h * vd * d


def expert_params(doc: dict) -> int:
    return 3 * doc["hidden_size"] * doc["moe_intermediate_size"]


def unrouted_params(doc: dict) -> int:
    """Every matrix a step reads whatever the router says, all kept layers."""
    d = doc["hidden_size"]
    total = 0
    for kind, dense in kinds(doc):
        total += kda_params(doc) if kind == "kda" else mla_params(doc)
        total += (3 * d * doc["intermediate_size"] if dense else
                  d * doc["num_experts_routed"] + 3 * d * doc["moe_shared_expert_intermediate_size"])
    return total


def state_bytes(doc: dict) -> int:
    """What one row keeps a KDA layer: ``S`` in float32 and the convolution's tail."""
    h, hd = doc["num_attention_heads"], doc["head_dim"]
    return h * hd * hd * F32 + (doc["short_conv_kernel_size"] - 1) * 3 * h * hd * BF16


def kda_flops(doc: dict, rows: list[tuple]) -> float:
    h, hd = doc["num_attention_heads"], doc["head_dim"]
    return 7.0 * h * hd * hd * n_kda(doc) * sum(n for n, _, _ in rows)


def kda_bytes(doc: dict, rows: list[tuple]) -> float:
    """Each fed row's state read and written once a KDA layer; a token's five
    operands (q, k, beta k, exp g, v) read and its output written, float32."""
    h, hd = doc["num_attention_heads"], doc["head_dim"]
    fed = [n for n, _, _ in rows if n > 0]
    return float(n_kda(doc) * (2 * state_bytes(doc) * len(fed) + 6 * h * hd * F32 * sum(fed)))


def kda_least_seconds(doc: dict, rows: list[tuple], peaks: dict) -> tuple[float, str]:
    return least(kda_flops(doc, rows), kda_bytes(doc, rows), peaks)


def walk_flops(doc: dict, rows: list[tuple]) -> float:
    return slot_key_flops(doc) * n_mla(doc) * sum(seen_positions(n, s) for n, s, _ in rows)


def walk_bytes(doc: dict, rows: list[tuple]) -> float:
    per = latent_dim(doc) * BF16 * n_mla(doc)
    return float(per * sum(s + n for n, s, _ in rows) + per * sum(n for n, _, _ in rows))


def step_flops(doc: dict, rows: list[tuple], assignments_here: int) -> float:
    """Multiply-adds x 2."""
    tokens = sum(n for n, _, _ in rows)
    head = 2.0 * doc["hidden_size"] * doc["vocab_size"] * sum(hd for _, _, hd in rows)
    return (2.0 * unrouted_params(doc) * tokens + 2.0 * expert_params(doc) * assignments_here
            + kda_flops(doc, rows) + walk_flops(doc, rows) + head)


def step_bytes(doc: dict, rows: list[tuple], touched: int) -> float:
    """HBM traffic.  ``touched``: (expert layer, held expert) pairs that got
    at least one token this step."""
    d = doc["hidden_size"]
    tokens = sum(n for n, _, _ in rows)
    weights = (unrouted_params(doc) + touched * expert_params(doc)) * BF16
    head = d * doc["vocab_size"] * BF16 if any(hd for _, _, hd in rows) else 0
    return float(weights + head + tokens * d * BF16 + kda_bytes(doc, rows)
                 + walk_bytes(doc, rows))


def step_least_seconds(doc: dict, rows: list[tuple], counters: dict, peaks: dict) -> tuple[float, str]:
    """``counters``: the step's expert counters as the program names them
    (``moe_assignments_here``, ``moe_experts_touched``)."""
    return least(step_flops(doc, rows, counters["moe_assignments_here"]),
                 step_bytes(doc, rows, counters["moe_experts_touched"]), peaks)
