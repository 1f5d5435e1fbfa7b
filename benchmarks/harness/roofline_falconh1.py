"""Operations and bytes one ragged serving step of the ``falcon_h1`` family
NEEDS, from its shapes and its rows: the family's own count
(``roofline.py`` knows a dense grouped-query layer and no state; here every
layer holds a Mamba-2 mixer beside its attention, whose cache is a STATE a
row and not positions).

As there, the count is the algorithm's, for the step's LIVE tokens only:

* every layer matrix once (``W_qkv``, ``Wo``, ``W_in``, ``W_out``, the three
  of the feed-forward) with the convolution's taps and bias, the
  recurrence's three parameters a head and the norms;
* the output head over the vocabulary slice once if any position needs
  logits; a token's embedding row;
* **the recurrence, from the step's ROWS and not from the form that computes
  them**: each fed row's state (``heads x d_head x d_state`` float32) read
  once and written once a layer whatever the row feeds; a token's ``x``,
  ``B``, ``C``, ``dt`` read and its ``y`` written, float32; and ``5 x heads x
  d_head x d_state`` operations a token and layer (the decay, the rank-one
  update's product and sum, the read-out's product and sum).  A chunk form on
  the MXU changes none of this;
* beside it in the STEP's count, each fed row's convolution tail read and
  written once a layer;
* K and V of every layer read once up to the row's last fed position, the new
  ones written; the attention's two products over each token's causal context.

``rows``: ``(tokens fed, start, positions that need logits)`` per sequence, as
the family's tap notes them.
"""
from __future__ import annotations

from benchmarks.harness.roofline_mla import BF16, least, seen_positions

F32 = 4  # bytes: the state and the recurrence's operands


def conv_dim(doc: dict) -> int:
    return doc["mamba_d_ssm"] + 2 * doc["mamba_n_groups"] * doc["mamba_d_state"]


def in_width(doc: dict) -> int:
    """Columns of ``W_in``: ``z | x | B | C | dt``."""
    return doc["mamba_d_ssm"] + conv_dim(doc) + doc["mamba_n_heads"]


def layer_matmul_params(doc: dict) -> int:
    """One layer's seven matrices (as stored: ``W_qkv`` and ``W_in`` fused)."""
    d, f = doc["hidden_size"], doc["intermediate_size"]
    q = doc["num_attention_heads"] * doc["head_dim"]
    kv = doc["num_key_value_heads"] * doc["head_dim"]
    return d * (q + 2 * kv) + q * d + d * in_width(doc) + doc["mamba_d_ssm"] * d + 3 * d * f


def layer_small_params(doc: dict) -> int:
    """Taps and bias, ``A_log`` / ``dt_bias`` / ``D``, the mixer's norm, the two norms."""
    return ((doc["mamba_d_conv"] + 1) * conv_dim(doc) + 3 * doc["mamba_n_heads"]
            + doc["mamba_d_ssm"] + 2 * doc["hidden_size"])


def state_elements(doc: dict) -> int:
    return doc["mamba_n_heads"] * doc["mamba_d_head"] * doc["mamba_d_state"]


def tail_bytes(doc: dict) -> int:
    return (doc["mamba_d_conv"] - 1) * conv_dim(doc) * BF16


def ssd_flops(doc: dict, rows: list[tuple]) -> float:
    return 5.0 * state_elements(doc) * doc["num_hidden_layers"] * sum(n for n, _, _ in rows)


def ssd_bytes(doc: dict, rows: list[tuple]) -> float:
    """Each fed row's state read and written once a layer; a token's x, B, C,
    dt read and its y written, float32."""
    fed = [n for n, _, _ in rows if n > 0]
    token = (2 * doc["mamba_d_ssm"] + 2 * doc["mamba_n_groups"] * doc["mamba_d_state"]
             + doc["mamba_n_heads"]) * F32
    return float(doc["num_hidden_layers"]
                 * (2 * state_elements(doc) * F32 * len(fed) + token * sum(fed)))


def ssd_least_seconds(doc: dict, rows: list[tuple], peaks: dict) -> tuple[float, str]:
    return least(ssd_flops(doc, rows), ssd_bytes(doc, rows), peaks)


def attention_flops(doc: dict, rows: list[tuple]) -> float:
    """QK^T and PV: 4 x heads x head_dim a query slot and visible key, a layer."""
    per = 4.0 * doc["num_attention_heads"] * doc["head_dim"] * doc["num_hidden_layers"]
    return per * sum(seen_positions(n, s) for n, s, _ in rows)


def kv_bytes(doc: dict, rows: list[tuple]) -> float:
    """K and V of every layer read up to the row's last fed position, the new ones written."""
    per = 2 * doc["num_key_value_heads"] * doc["head_dim"] * BF16 * doc["num_hidden_layers"]
    return float(per * sum(s + n for n, s, _ in rows) + per * sum(n for n, _, _ in rows))


def step_flops(doc: dict, rows: list[tuple]) -> float:
    """Multiply-adds x 2."""
    tokens = sum(n for n, _, _ in rows)
    head = 2.0 * doc["hidden_size"] * doc["vocab_size"] * sum(hd for _, _, hd in rows)
    return (2.0 * layer_matmul_params(doc) * doc["num_hidden_layers"] * tokens
            + attention_flops(doc, rows) + ssd_flops(doc, rows) + head)


def step_bytes(doc: dict, rows: list[tuple]) -> float:
    """HBM traffic."""
    d, n_layers = doc["hidden_size"], doc["num_hidden_layers"]
    tokens = sum(n for n, _, _ in rows)
    fed = sum(1 for n, _, _ in rows if n > 0)
    weights = ((layer_matmul_params(doc) + layer_small_params(doc)) * n_layers + d) * BF16
    head = d * doc["vocab_size"] * BF16 if any(hd for _, _, hd in rows) else 0
    return float(weights + head + tokens * d * BF16 + ssd_bytes(doc, rows)
                 + 2 * tail_bytes(doc) * n_layers * fed + kv_bytes(doc, rows))


def step_least_seconds(doc: dict, rows: list[tuple], peaks: dict) -> tuple[float, str]:
    return least(step_flops(doc, rows), step_bytes(doc, rows), peaks)
