"""Operations and bytes one ragged serving step of the ``xing`` family NEEDS:
``roofline_mla``'s count of A.X-K1's step (the family's sublayers ARE that
family's: unrouted weights once, each touched expert once, each row's latent
once a layer, the walks in absorbed form, the head once if a position is
sampled) plus what is this family's own, the hyper-connected stream's maps.

As there, the count is the algorithm's, for the step's LIVE tokens only, and
**the maps' bytes are what the algorithm must bring from HBM: each sublayer's
``phi`` once a step, and NOTHING for the stream.**  ISSUE 49 asked for the
fused form's traffic of the stream (``(2 n C + 2 C) x 4 B`` = 143 KB a live
token and sublayer) and the chip refused that count (my chip run, PR 49:
``mhc_roofline_share`` read 143.9 % by it): a serving buffer's stream is 256
slots x 57 KB = 14.7 MB, XLA's memory-space assignment keeps it in the
core's VMEM from one kernel to the next (``S(1)`` on the kernels' stream
operands and results in the program compiled for a v5e), and the traced
kernels move their 14.7 and 33 MB in 13 and 16 us, 1.1 and 2.0 TB/s, past
the HBM's 819 GB/s.  So the stream need not cross HBM at all, as the other
activations of a step do not (``roofline_mla.step_bytes`` counts none
either), and what is left binds by its operations, at the MXU's peak: a floor
no fusion can pass and no kernel of float32 vector work will come near (the
chip's vector and VMEM peaks are not in ``harness/peaks.py``).  Padded buffer
slots the kernels compute are the program's, not the algorithm's, and are
not counted.

Operations a live token and sublayer: the projection ``2 x n C x n (n + 2)``,
the contraction ``2 n C``, the mixing ``2 n^2 C`` and the expansion ``2 n C``,
and Sinkhorn's ``iters x 2`` normalisations of ``n^2`` entries (an add, a
product and their share of ``n`` reciprocals each: 3 a number).
"""
from __future__ import annotations

from benchmarks.harness import roofline_mla
from benchmarks.harness.roofline_mla import BF16, least

def sublayers(doc: dict) -> int:
    """Sublayers of the cut, each between an open and a close."""
    return 2 * doc["num_hidden_layers"]


def live_tokens(rows: list[tuple]) -> int:
    return sum(n for n, _, _ in rows)


def maps_rows(doc: dict) -> int:
    """Numbers of the three maps a token: ``n + n + n^2``."""
    return doc["hc_mult"] * (doc["hc_mult"] + 2)


def mhc_flops(doc: dict, rows: list[tuple]) -> float:
    n, c = doc["hc_mult"], doc["hidden_size"]
    per_token = (2.0 * n * c * maps_rows(doc) + 2.0 * n * c + 2.0 * n * n * c + 2.0 * n * c
                 + doc["hc_sinkhorn_iters"] * 2 * 3.0 * n * n)
    return sublayers(doc) * per_token * live_tokens(rows)


def mhc_bytes(doc: dict, rows: list[tuple]) -> float:
    """HBM traffic the maps need (see the module docstring): each sublayer's
    ``phi`` once if a token is live, the stream not at all."""
    phi = maps_rows(doc) * doc["hc_mult"] * doc["hidden_size"] * BF16
    return float(sublayers(doc) * phi) if live_tokens(rows) else 0.0


def mhc_least_seconds(doc: dict, rows: list[tuple], peaks: dict) -> tuple[float, str]:
    return least(mhc_flops(doc, rows), mhc_bytes(doc, rows), peaks)


def step_flops(doc: dict, rows: list[tuple], assignments_here: int) -> float:
    return roofline_mla.step_flops(doc, rows, assignments_here) + mhc_flops(doc, rows)


def step_bytes(doc: dict, rows: list[tuple], touched: int) -> float:
    return roofline_mla.step_bytes(doc, rows, touched) + mhc_bytes(doc, rows)


def step_least_seconds(doc: dict, rows: list[tuple], counters: dict, peaks: dict) -> tuple[float, str]:
    """``counters``: the step's expert counters as the program names them
    (``moe_assignments_here``, ``moe_experts_touched``)."""
    return least(step_flops(doc, rows, counters["moe_assignments_here"]),
                 step_bytes(doc, rows, counters["moe_experts_touched"]), peaks)
