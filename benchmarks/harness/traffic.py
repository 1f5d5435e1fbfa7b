"""The one general traffic generator: a traffic file's parameters and
``--seed`` give the requests, and nothing else does (no jax, no clock).

The SCHEDULE is the mix's, not the seed's: prompt lengths, answer lengths
and inter-arrival gaps are evenly spaced quantiles of the stated
distributions, put in one order by the traffic file's own
``schedule_seed``.  ``--seed`` draws the token ids (and, elsewhere, the
weights).  So every seed offers the same requests at the same times with
other contents, and runs on different seeds measure the same load.  With a
few dozen long requests in a window, a schedule reshuffled by the seed moved
the median TTFT by 8 % and the tokens per second by 12 % from seed to seed
(PERF.md, PR 23): more than any change to the program is allowed to.  A mix
in another order is another traffic file with another ``schedule_seed``.

A request is a dict: ``i``; ``session`` and ``turn``; ``session_id``;
``tokens`` (turn 0: shared prefix + new prompt tokens; later turns: the new
tokens only, which the load generator appends to prompt + answer of the
turn before); ``max_new_tokens``; and either ``due_s`` (offset from the
window's start, open loop, turn 0) or ``after`` (index of the turn it
follows) with ``think_s``.  Closed-loop requests have neither: clients pull
them in order.
"""
from __future__ import annotations

import math
import random
from statistics import NormalDist


def quantiles(dist: dict, n: int) -> list[float]:
    """``n`` evenly spaced quantiles ((i + 0.5) / n) of a distribution."""
    kind = dist["dist"]
    us = [(i + 0.5) / n for i in range(n)]
    if kind == "fixed":
        return [float(dist["value"])] * n
    if kind == "uniform":
        return [dist["min"] + u * (dist["max"] - dist["min"]) for u in us]
    if kind == "lognormal":
        nd = NormalDist()
        return [dist["median"] * math.exp(dist["sigma"] * nd.inv_cdf(u)) for u in us]
    if kind == "exponential":
        return [-dist["mean"] * math.log(1.0 - u) for u in us]
    raise ValueError(f"unknown distribution {kind!r}")


def lengths(dist: dict, n: int) -> list[int]:
    lo, hi = int(dist["min"]), int(dist["max"])
    return [min(hi, max(lo, round(x))) for x in quantiles(dist, n)]


def arrival_gaps(traffic: dict, seconds: float, base: random.Random) -> list[float]:
    """Open loop: the gaps between sessions' first turns, summing to
    ``seconds``.  ``poisson``: exponential gaps (quantiles, shuffled by the
    schedule's own seed, scaled to fill the window exactly); ``bursts``:
    ``size`` at once every ``period_s``."""
    arr = traffic["arrivals"]
    if arr["process"] == "bursts":
        n_bursts = max(1, int(seconds // arr["period_s"]))
        burst = [0.0] * (int(arr["size"]) - 1) + [float(arr["period_s"])]
        return burst * n_bursts
    if arr["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    n = max(1, round(traffic["rate_rps"] * seconds))
    gaps = quantiles({"dist": "exponential", "mean": 1.0}, n)
    base.shuffle(gaps)
    scale = seconds / sum(gaps)
    return [g * scale for g in gaps]


def _tokens(rng: random.Random, n: int, vocab: int) -> list[int]:
    return [rng.randrange(1, vocab) for _ in range(n)]


def generate(traffic: dict, *, seed: int, seconds: float, vocab: int, context: int,
             max_new_cap: int) -> list[dict]:
    """All requests of one run."""
    rng = random.Random(int(seed))
    base = random.Random(int(traffic.get("schedule_seed", 0)))  # the schedule's own order
    loop = traffic["loop"]
    if loop == "open":
        gaps = arrival_gaps(traffic, seconds, base)
        n_sessions = len(gaps)
        blocks = [n_sessions]
    elif loop == "closed":
        gaps = None
        # the queue the clients pull from: whole blocks, each the same
        # multiset in another order, so any stretch of it is the same mix
        block = int(traffic["block"])
        n_sessions = block * max(1, math.ceil(traffic["pool_rps"] * seconds / block))
        blocks = [block] * (n_sessions // block)
    else:
        raise ValueError(f"loop must be open or closed, not {loop!r}")

    ses = traffic.get("sessions") or {}
    t_lo, t_hi = ses.get("turns", [1, 1])
    th_lo, th_hi = ses.get("think_s", [0.0, 0.0])
    shared = _tokens(rng, int(ses.get("shared_prefix_tokens", 0)), vocab)

    def dealt(dist: dict, n_per: int = 1) -> list[int]:
        out: list[int] = []
        for b in blocks:
            part = lengths(dist, b * n_per)
            base.shuffle(part)
            out.extend(part)
        return out

    max_turns = int(t_hi)
    prompt_lens = dealt(traffic["prompt_tokens"], max_turns)
    new_lens = dealt(traffic["new_tokens"], max_turns)
    turn_counts = dealt({"dist": "uniform", "min": t_lo, "max": t_hi})

    dues, t = [], 0.0
    for g in gaps or ():
        dues.append(t)
        t += g

    requests: list[dict] = []
    for s in range(n_sessions):
        used = 0
        prev = None
        for turn in range(turn_counts[s]):
            k = s * max_turns + turn
            n_new = min(new_lens[k], max_new_cap)
            head = len(shared) if turn == 0 else 0
            if used + head + prompt_lens[k] + n_new > context:
                if turn == 0:  # clip a first prompt to the pool's context
                    prompt_lens[k] = context - n_new - head
                else:
                    break
            req = {"i": len(requests), "session": s, "turn": turn,
                   "session_id": f"s{seed}-{s}",
                   "tokens": (shared if turn == 0 else []) + _tokens(rng, prompt_lens[k], vocab),
                   "max_new_tokens": n_new}
            if turn > 0:
                req["after"] = prev
                req["think_s"] = th_lo + rng.random() * (th_hi - th_lo)
            elif gaps is not None:
                req["due_s"] = dues[s]
            prev = req["i"]
            used += head + prompt_lens[k] + n_new
            requests.append(req)
    return requests
