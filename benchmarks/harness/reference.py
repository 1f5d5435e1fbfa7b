"""The check of outputs: what decides ``correct``.

After the window has closed, a sample of the requests it finished (drawn
from the seed, the longest always in it, some hundreds of served tokens) is
run ONCE EACH through the family's plain float32 reference, teacher-forced
on prompt + served tokens, and every served token's logit is held against
the reference's best logit at that position.  Greedy decoding makes the
served token the program's own best; in exact arithmetic the gap is 0.

Numbers compared, each printed beside its limit in every run:

* ``gap_mean``  mean over the sampled served tokens of (reference's best
  logit - reference's logit of the served token).  Steady from seed to
  seed; grows with the square of the program's numerical error, so it is
  the number a lower precision fails.
* ``gap_max``   the widest such gap.  Swings by its nature; its limit
  catches a single wrong token (a random token lies 3-5 below the best).
* ``stream_faults``  requests whose stream had a gap, a repeat, a wrong
  count, or differed from the terminal result: limit 0.
* ``unfinished``  requests due in the window that did not reach SUCCEEDED
  by the end of the drain: limit 0.
* ``window_compiles``  compile requests inside the window: limit 0.

The limits of the two gaps are in the configuration file (``check``), set
from chip readings recorded in PERF.md.
"""
from __future__ import annotations

import random


def pick_sample(records: list[dict], seed: int, min_tokens: int, max_requests: int) -> list[dict]:
    """Finished requests to re-run: the longest (prompt + served), then
    others in an order drawn from the seed until ``min_tokens`` served
    tokens are covered."""
    done = [r for r in records if r.get("state") == "SUCCEEDED" and r["n_tokens"] > 0]
    if not done:
        return []
    longest = max(done, key=lambda r: (r["prompt_len"] + r["n_tokens"], -r["i"]))
    rest = [r for r in done if r is not longest]
    random.Random(int(seed) ^ 0x5EED).shuffle(rest)
    out, n = [longest], longest["n_tokens"]
    for r in rest:
        if n >= min_tokens or len(out) >= max_requests:
            break
        out.append(r)
        n += r["n_tokens"]
    return out


def gaps_of(ref, params, sample: list[dict], *, control: bool = False) -> dict:
    """Per served token of the sample: reference's best logit minus its
    logit of the served token.  With ``control`` the 'served' token is
    instead the one the family's lower-precision forward puts first at that
    position (same prompts, same tokens: it need not decode)."""
    import numpy as np

    gaps: list[float] = []
    mismatches = 0
    for rec in sample:
        seq = list(rec["prompt"]) + list(rec["tokens"])
        inp, nxt = seq[:-1], seq[1:]
        lo = len(rec["prompt"]) - 1  # position whose next token is the first served
        if control:
            _, arg_c, _ = ref.logits_of(params, inp, nxt, lower_precision=True)
            nxt = [int(t) for t in arg_c]
        top, arg, got = ref.logits_of(params, inp, nxt)
        if not np.isfinite(top).all():
            raise FloatingPointError("reference logits are not finite")
        g = (top - got)[lo:]
        gaps.extend(float(x) for x in g)
        mismatches += int((np.asarray(nxt)[lo:] != arg[lo:]).sum())
    return {"tokens": len(gaps), "requests": len(sample),
            "gap_mean": sum(gaps) / len(gaps) if gaps else float("nan"),
            "gap_max": max(gaps) if gaps else float("nan"),
            "mismatch_share": mismatches / len(gaps) if gaps else float("nan")}


def stream_faults(records: list[dict]) -> list[dict]:
    """Every token exactly once and in order; the stream equals the result."""
    bad = []
    for r in records:
        if r.get("state") != "SUCCEEDED":
            continue
        why = []
        if r["gaps"]:
            why.append(f"{r['gaps']} gap(s)")
        if r["dups"]:
            why.append(f"{r['dups']} repeated token(s)")
        if r["n_tokens"] != r["want"]:
            why.append(f"{r['n_tokens']} tokens streamed, {r['want']} asked")
        if not r.get("stream_equals_result"):
            why.append("stream differs from the terminal result")
        if why:
            bad.append({"i": r["i"], "why": "; ".join(why)})
    return bad


def verdict(numbers: dict, limits: dict) -> tuple[bool, list[dict]]:
    """Each number beside its limit; correct iff every number is within."""
    rows = []
    for name, limit in limits.items():
        value = numbers[name]
        ok = value == value and value <= limit  # NaN fails
        rows.append({"number": name, "value": value, "limit": limit, "ok": ok})
    return all(r["ok"] for r in rows), rows
