"""Metric arithmetic on the load generator's per-request records.  Pure
Python, no jax: the yardstick that later PRs cannot change."""
from __future__ import annotations

import math
from typing import Iterable, Optional


def percentile(values: Iterable[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics (numpy's default rule)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def median(values: Iterable[float]) -> float:
    return percentile(values, 50.0)


def ttft_s(rec: dict, loop: str) -> Optional[float]:
    """Open loop: from when the request was DUE; closed loop: from when it
    was sent.  None if no token ever came."""
    if rec.get("first") is None:
        return None
    origin = rec["due"] if loop == "open" else rec["sent"]
    return rec["first"] - origin


def tpot_s(rec: dict) -> Optional[float]:
    """(last token time - first token time) / (tokens - 1); None under two
    tokens.  A multi-token speculative packet is averaged over, not counted
    as zero-gap tokens."""
    n = rec.get("n_tokens", 0)
    if n < 2 or rec.get("first") is None or rec.get("last") is None:
        return None
    return (rec["last"] - rec["first"]) / (n - 1)


def tokens_in_window(records: list[dict], t0: float, t1: float) -> int:
    """Output tokens that reached the client in [t0, t1)."""
    return sum(n for r in records for t, n in r.get("packets", ()) if t0 <= t < t1)


def end_to_end(records: list[dict], *, loop: str, t0: float, window_s: float) -> dict:
    """The end-to-end metrics over every request due (or sent) in the
    window.  A request with no first token has no TTFT; it is counted under
    ``failed`` and makes the run incorrect, so no tail is taken without it
    being seen.  A request a closed loop sent in its ramp, before the window,
    gives no TTFT and no TPOT; its tokens that arrive inside the window count
    for the rate like any others."""
    mine = [r for r in records if not r.get("ramp")]
    ttfts = [v for v in (ttft_s(r, loop) for r in mine) if v is not None]
    tpots = [v for v in (tpot_s(r) for r in mine) if v is not None]
    out = {"ttft_samples": len(ttfts), "tpot_samples": len(tpots)}
    if ttfts:
        out["ttft_p50_ms"] = 1e3 * percentile(ttfts, 50)
        out["ttft_p95_ms"] = 1e3 * percentile(ttfts, 95)
    if tpots:
        out["tpot_p95_ms"] = 1e3 * percentile(tpots, 95)
    out["tokens_per_s"] = tokens_in_window(records, t0, t0 + window_s) / window_s
    return out
