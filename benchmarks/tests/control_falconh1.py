#!/usr/bin/env python
"""Hand-run, on the chip: the control of a ``falcon_h1`` cell's recurrent
state, the knee sweep's period, and the state probe.

    python benchmarks/tests/control_falconh1.py bf16 --workload falconh1-chatbursts-open \\
        --seed <n> --seconds 51 --trace 0
    python benchmarks/tests/control_falconh1.py period <P> --workload falconh1-chatbursts-open \\
        --seed <n> --seconds 102 --trace 0
    python benchmarks/tests/control_falconh1.py state --workload falconh1-chatbursts-open \\
        --seed <n> [--prompt 640] [--decode 64] [--rehearse]

``bf16`` is one run of ``benchmarks/run.py`` with the BROKEN program the cell's
check must tell from the sound one: every layer's state rounded to bfloat16
behind every step (``jax.lax.reduce_precision``: a pair of casts is dropped by
the TPU compiler, PERF.md section 6, PR 40).  The broken program is built HERE
(:func:`broken` wraps ``cordum_tpu.models.ssd.mixer`` while the program is
traced); the program itself has no such option.  The other control, int8
weights, is ``run.py --control 1`` (the reference's ``lower_precision``).

``period <P>`` is one run of ``benchmarks/run.py`` with the traffic file's
``arrivals.period_s`` replaced by ``P`` (the knee sweep: ``run.py --rate``
moves a Poisson rate, and a burst process has none), and one more line printed
(``{"phase": "bursts"}``: the client's TTFT median and p95, the requests in
flight at each burst's instant, each burst's absorption time: what the knee is
read from); nothing else differs.

``state`` is the number the served tokens may not show: one row served through
the backend at the file's widths and buffer (its prompt in chunks of the
prefill budget, then decode steps), and the state its slot then holds, a layer
at a time, against the reference's token-by-token scan over the same tokens:
``|S - S_ref|_F / |S_ref|_F``, for the sound program and for ``bf16``.
"""
import argparse
import contextlib
import json
import os
import random
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


@contextlib.contextmanager
def broken(control: str):
    """While inside, a ``falcon_h1`` program that is traced is the broken
    one: ``"bf16"`` rounds a layer's state to bfloat16's eight bits behind
    every step; ``""`` changes nothing."""
    import jax

    from cordum_tpu.models import ssd

    sound = ssd.mixer

    def rounded_state(u, layer, state, tail, row, rows, cfg):
        out, state, tail = sound(u, layer, state, tail, row, rows, cfg)
        return out, state.at[row].set(jax.lax.reduce_precision(state[row], 8, 7)), tail

    if control == "bf16":
        ssd.mixer = rounded_state
    elif control:
        raise ValueError(control)
    try:
        yield
    finally:
        ssd.mixer = sound


@contextlib.contextmanager
def period(seconds: float):
    """While inside, every traffic file's bursts are ``seconds`` apart."""
    from benchmarks.harness import cells

    load = cells.load_traffic

    def with_period(name):
        doc = load(name)
        return {**doc, "arrivals": {**doc["arrivals"], "period_s": seconds},
                "rate_rps": doc["arrivals"]["size"] / seconds}

    cells.load_traffic = with_period
    try:
        yield
    finally:
        cells.load_traffic = load


@contextlib.contextmanager
def burst_report():
    """While inside, a run also prints what the knee is read from and what the
    harness computes but does not print (``run.py`` prints the client's median
    TTFT on its ``window`` line and no tail): one line ``{"phase": "bursts"}``
    with the client's TTFT median and p95, the requests in flight at each
    burst's instant (those due before it and not yet done), and each burst's
    absorption time (its last first token behind its instant).  Reads the
    records ``stats.end_to_end`` is handed; changes nothing of the run."""
    from benchmarks.harness import stats

    inner = stats.end_to_end

    def reported(records, **kw):
        out = inner(records, **kw)
        mine = [r for r in records if not r.get("ramp")]
        at = sorted({r["due"] for r in mine})
        in_flight = [sum(1 for r in mine if r["due"] < b and (r["done"] is None or r["done"] > b))
                     for b in at]
        absorb = [max((r["first"] - b for r in mine if r["due"] == b and r["first"] is not None),
                      default=None) for b in at]
        print(json.dumps({"phase": "bursts", "client_ttft_p50_ms": out.get("ttft_p50_ms"),
                          "client_ttft_p95_ms": out.get("ttft_p95_ms"),
                          "in_flight_at_bursts": in_flight,
                          "absorb_s": [None if a is None else round(a, 3) for a in absorb]}),
              flush=True)
        return out

    stats.end_to_end = reported
    try:
        yield
    finally:
        stats.end_to_end = inner


def served_state(cfg, params, pool: dict, prompt: list[int], decode: int):
    """One row through a backend of the pool's buffer: ``prompt`` in chunks of
    the prefill budget, then ``decode`` steps on the program's own tokens.
    Returns ``(the row's state, a layer at a time [L, heads, d_head, d_state],
    the tokens it fed)``."""
    import numpy as np

    from cordum_tpu.serving.backend import ServingBackend, StepEntry

    per = cfg.max_seq_len // pool["page_size"]
    be = ServingBackend(cfg, num_pages=per + 1, page_size=pool["page_size"],
                        max_seqs=pool["max_sessions"],
                        max_batch_tokens=pool["max_sessions"] + pool["prefill_budget"],
                        params=params)
    pages, slot = list(range(1, per + 1)), 1
    fed, nxt = [], None
    for at in range(0, len(prompt), pool["prefill_budget"]):
        chunk = prompt[at:at + pool["prefill_budget"]]
        (nxt,) = be.step([StepEntry(tokens=chunk, start=at, pages=pages, phase="prefill",
                                    sample=at + len(chunk) == len(prompt), state_slot=slot)])
        fed.extend(chunk)
    for _ in range(decode):
        fed.append(int(nxt))
        (nxt,) = be.step([StepEntry(tokens=[fed[-1]], start=len(fed) - 1, pages=pages,
                                    state_slot=slot)])
    state = np.asarray(be._arenas[2][:, slot])  # [L, d_state, heads, d_head]: the kernel's layout
    be.release_arenas()
    return state.transpose(0, 2, 3, 1), fed


def state_probe(argv: list[str]) -> int:
    import jax
    import numpy as np

    from benchmarks import run as bench_run
    from benchmarks.harness import cells

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prompt", type=int, default=640)
    ap.add_argument("--decode", type=int, default=64)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = cells.resolve(args.workload)
    doc = dict(cell.config)
    if args.rehearse:
        doc.update(bench_run.TINY)
    fam = cell.family
    cfg = fam.program_config(doc)
    params = jax.block_until_ready(fam.make_params(doc, args.seed))
    ref = fam.reference.Reference(doc, doc["max_position_embeddings"])
    rng = random.Random(args.seed)
    prompt = [rng.randrange(1, doc["vocab_size"]) for _ in range(args.prompt)]
    for control in ("", "bf16"):
        with broken(control):
            got, fed = served_state(cfg, params, doc["pool"], prompt, args.decode)
        want = np.stack(ref.ssm_states(params, fed))
        err = [float(np.linalg.norm(g - w) / np.linalg.norm(w)) for g, w in zip(got, want)]
        print(json.dumps({"program": control or "sound", "seed": args.seed, "tokens": len(fed),
                          "platform": jax.default_backend(), "state_err_by_layer": err,
                          "state_err_max": max(err)}), flush=True)
    return 0


def main() -> int:
    if len(sys.argv) < 2 or sys.argv[1] not in ("bf16", "period", "state"):
        print(__doc__, file=sys.stderr)
        return 2
    mode = sys.argv.pop(1)
    if mode == "state":
        return state_probe(sys.argv[1:])
    from benchmarks import run as bench_run

    if mode == "period":
        with period(float(sys.argv.pop(1))), burst_report():
            return bench_run.main()
    with broken(mode):
        return bench_run.main()


if __name__ == "__main__":
    sys.exit(main())
