#!/usr/bin/env python
"""Hand-run, on the chip: the controls of a ``bailing`` cell's recurrent state.

    python benchmarks/tests/control_bailing.py <zero|bf16> --workload ling3-reasoning-open \\
        --seed <n> --seconds 51 --trace 0
    python benchmarks/tests/control_bailing.py state --workload ling3-reasoning-open \\
        --seed <n> [--prompt 1984] [--decode 64] [--rehearse]

``zero`` and ``bf16`` are one run of ``benchmarks/run.py`` with one of the two
BROKEN programs the cell's check must tell from the sound one.  ``zero``:
every row starts from a zero state in every step (a stale state: the served
tokens are those of a model that forgets everything but the latent layer's
pages and the convolution's three positions); the run must come out NOT
``correct`` by the cell's limits.  ``bf16``: the state rounded to bfloat16
behind every step; reported beside it.  The broken programs are built HERE
(:func:`broken` wraps two functions of ``cordum_tpu.models.kda`` while the
program is traced); the program itself has no such option.

``state`` is the number the served tokens cannot show (the routing's near-ties
mask a rounded state: PERF.md section 6, PR 40): one row served through the
backend at the file's widths and buffer (its prompt in chunks of the prefill
budget, then decode steps), and the state its slot then holds, a KDA layer at
a time, against the reference's token-by-token scan over the same tokens:
``|S - S_ref|_F / |S_ref|_F``, for the sound program and for ``bf16``.  One
JSON line a program.  ``benchmarks/harness/reference.py`` compares five fixed
numbers, so this one decides no ``correct`` yet (PERF.md section 7).
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
    """While inside, a ``bailing`` program that is traced is the broken one:
    ``"zero"`` makes every row of every step a fresh one, ``"bf16"`` rounds a
    KDA layer's state to bfloat16's eight bits behind every step; ``""``
    changes nothing."""
    import jax
    import jax.numpy as jnp

    from cordum_tpu.models import kda

    sound = kda.state_rows, kda.kda_sublayer

    def every_row_fresh(*args):
        rows = sound[0](*args)
        return rows._replace(fresh=jnp.ones_like(rows.fresh))

    def rounded_state(a, layer, state, tail, row, rows, cfg):
        o, state, tail = sound[1](a, layer, state, tail, row, rows, cfg)
        # ``reduce_precision`` and not a pair of casts: the TPU compiler drops a float32 ->
        # bfloat16 -> float32 round trip as excess precision it may keep (PERF.md section 6)
        return o, state.at[row].set(jax.lax.reduce_precision(state[row], 8, 7)), tail

    if control == "zero":
        kda.state_rows = every_row_fresh
    elif control == "bf16":
        kda.kda_sublayer = rounded_state
    elif control:
        raise ValueError(control)
    try:
        yield
    finally:
        kda.state_rows, kda.kda_sublayer = sound


def served_state(cfg, params, pool: dict, prompt: list[int], decode: int):
    """One row through a backend of the pool's buffer: ``prompt`` in chunks of
    the prefill budget, then ``decode`` steps on the program's own tokens.
    Returns ``(the row's state, a KDA layer at a time [L, h, d_k, d_v], the
    tokens it fed)``."""
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
    state = np.asarray(be._arenas[1][:, slot])  # [L, d_k, h, d_v]: the kernel's layout
    be.release_arenas()
    return state.transpose(0, 2, 1, 3), fed


def state_probe(argv: list[str]) -> int:
    import jax
    import numpy as np

    from benchmarks import run as bench_run
    from benchmarks.harness import cells

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prompt", type=int, default=1984)
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
        want = np.stack(ref.kda_states(params, fed))
        err = [float(np.linalg.norm(g - w) / np.linalg.norm(w)) for g, w in zip(got, want)]
        print(json.dumps({"program": control or "sound", "seed": args.seed, "tokens": len(fed),
                          "platform": jax.default_backend(), "state_err_by_layer": err,
                          "state_err_max": max(err)}), flush=True)
    return 0


def main() -> int:
    if len(sys.argv) < 2 or sys.argv[1] not in ("zero", "bf16", "state"):
        print(__doc__, file=sys.stderr)
        return 2
    control = sys.argv.pop(1)
    if control == "state":
        return state_probe(sys.argv[1:])
    from benchmarks import run as bench_run

    with broken(control):
        return bench_run.main()


if __name__ == "__main__":
    sys.exit(main())
