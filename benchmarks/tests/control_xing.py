#!/usr/bin/env python
"""Hand-run, on the chip: the controls of a ``xing`` cell.

    python benchmarks/tests/control_xing.py <mode> --workload xing4-ragqa-open \\
        --seed <n> --seconds 51 --trace 0 [--control 1]

``static``  one run of ``benchmarks/run.py`` with the program as it is; the
            sampled requests are ALSO read by the reference with the three
            ``alpha`` of every sublayer's maps at 0 (``Reference(...,
            static_maps=True)``: the maps a program would compute had it left
            the token-dependent part out), and the run prints a ``{"phase":
            "static"}`` line with that reading beside the cell's limits.  It
            must read not ``correct``.  With ``--control 1`` the same run
            reads the int8 control too: three readings of one sample.
``nomaps``  one run with a BROKEN program, built HERE while the program is
            traced (the program has no such option): ``hyper.maps_of`` is
            handed ``alpha`` 0, so the served program computes the static
            maps alone.  The run must end ``correct: false``.
``plain``   one run with the maps in their ``jax.numpy`` form ON THE CHIP
            (``hyper.fits`` says no while the program is traced, so the step
            holds no ``mhc_*`` kernel and is not padded to their tiles): the
            kernels' yardstick inside the whole program, where their operands
            live in VMEM and a lone call's agreement does not reach.  Its
            ``gap_mean`` should read what the sound program's reads.
``sound``   the program as it is, nothing more (``run.py`` itself).
``budget``  ``budget <n>``: the program as it is at ANOTHER prefill budget (the
            pool's ``prefill_budget`` is ``n`` for this run): the knee sweep's
            second axis, which ``run.py`` has no option for.

The control the limits are SET against is the int8 one (``run.py --control
1``); these two guard the new mechanism: ``check.derivation`` in the
configuration file records their readings.
"""
import contextlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

MODES = ("static", "nomaps", "plain", "sound", "budget")


@contextlib.contextmanager
def broken(control: str):
    """While inside, a ``xing`` program that is traced is the broken one
    (``""`` and the modes that serve the sound program change nothing)."""
    from cordum_tpu.models import hyper

    maps_of, fits = hyper.maps_of, hyper.fits
    if control == "nomaps":
        hyper.maps_of = lambda z, alpha, bias, hc: maps_of(z, [0.0 * a for a in alpha], bias, hc)
    elif control == "plain":
        hyper.fits = lambda n, width: False
    elif control not in ("", "static", "sound", "budget"):
        raise ValueError(control)
    try:
        yield
    finally:
        hyper.maps_of, hyper.fits = maps_of, fits


@contextlib.contextmanager
def static_said():
    """While inside, the harness's reading of the sample is followed by the
    static-maps reference's reading of the same sample, printed beside the
    limits the run's own check uses."""
    from benchmarks import run as bench_run
    from benchmarks.harness import reference

    gaps_of = reference.gaps_of

    def said(ref, params, sample, *, control=False):
        out = gaps_of(ref, params, sample, control=control)
        if not control:
            static = gaps_of(type(ref)(ref.doc, ref.pad_to, static_maps=True), params, sample)
            chk = ref.doc["check"]
            limits = {"gap_mean": chk["gap_mean_limit"], "gap_max": chk["gap_max_limit"]}
            ok, rows = reference.verdict(static, limits)
            bench_run.say(phase="static", correct=ok, compared=rows,
                          mismatch_share=static["mismatch_share"], sound_gap_mean=out["gap_mean"])
        return out

    reference.gaps_of = said
    try:
        yield
    finally:
        reference.gaps_of = gaps_of


@contextlib.contextmanager
def budget_of(n: int):
    """While inside, every configuration file is read with a prefill budget of ``n``."""
    from benchmarks.harness import cells

    load_config = cells.load_config

    def loaded(name):
        doc = load_config(name)
        return {**doc, "pool": {**doc["pool"], "prefill_budget": n}}

    cells.load_config = loaded
    try:
        yield
    finally:
        cells.load_config = load_config


def main() -> int:
    if len(sys.argv) < 2 or sys.argv[1] not in MODES:
        print(__doc__, file=sys.stderr)
        return 2
    mode = sys.argv.pop(1)
    from benchmarks import run as bench_run

    with contextlib.ExitStack() as held:
        held.enter_context(broken(mode))
        if mode == "static":
            held.enter_context(static_said())
        if mode == "budget":
            held.enter_context(budget_of(int(sys.argv.pop(1))))
        return bench_run.main()


if __name__ == "__main__":
    sys.exit(main())
