#!/usr/bin/env python
"""Hand-run, on the chip: the controls of a ``mellum`` cell.

    python benchmarks/tests/control_mellum.py <mode> --workload mellum2-idechat-open \\
        --seed <n> --seconds 51 --trace 0

Each is one run of ``benchmarks/run.py`` with a BROKEN program whose reading the
cell's check is set beside (``check.derivation`` in the configuration file), built
HERE while the program is traced; the program itself has no such option.
Rounding is ``jax.lax.reduce_precision``: a pair of casts is dropped by the
TPU compiler (PERF.md section 6, PR 40).

PLANTED FAULTS, the ones the cell's attention side exists to catch:

``noyarn``   the full kind rotated plainly under its theta: no YaRN blend of
             the frequencies, no factor on cos and sin.
``ringpage`` every window layer's walk reads ring column 6's page where it
             should read column 5's: 16 of the 1024 keys a lapped ring holds
             are another page's, at every position whose window reaches the
             column (four in five).
``token``    the token the program puts first is altered (+1) at every
             position that is 255 modulo 256: four or five of a sample's 1000
             served tokens, each a token the reference did not choose.

On the chip (PR 46, one run each at the cell's sizes, seeds 4600000411-413) the
check refuses all three: ``gap_mean`` 0.02411, 0.02938, 0.02881 against a limit of
0.0055, and ``token``'s ``gap_max`` 5.274 against 1.5.

``sound``    the program as it is; the run also prints what ``run.py`` does
             not: the client's TTFT p50 and p95 (no end-to-end TTFT metric
             lists this cell), the percentiles of the requests' TPOT and the
             eight requests with the highest, each with when it decoded.

LOWER PRECISION in the served program:

``fp8``  the rotated q and k of every layer rounded to an 8-bit float's three
         mantissa bits (e4m3's; the exponent is left wide), the K that goes
         into both kinds of page with them: a cache and a walk in the nearest
         precision below bfloat16 that a deployment would try.
``bf16`` the normed float32 stream that the router and the experts read
         rounded to bfloat16: the program as it would be with a bfloat16
         residual stream (what ``models/afmoe.py`` had before PR 26's review).
         It breaks near-ties among the router's scores differently from the
         float32 reference.

Neither of these two need fail, and on the chip neither does (PR 46, one run
each: ``fp8`` read ``gap_mean`` 0.00363 and ``bf16`` 0.00267 where 15 sound runs
with as large a sample read 0.00077-0.00227 and the limit is 0.0055): their
readings say how much of
``gap_mean`` is the attention's and how much the router's, which is what the
configuration's ``check.derivation`` and PERF.md section 7 record.  The control
the limits are SET against is the third one, int8 products: ``run.py
--control 1`` (the reference's ``lower_precision``), the reference's own int8
choice at every sampled position, no program run.

"""
import contextlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

MODES = ("fp8", "bf16", "noyarn", "ringpage", "token", "sound")


@contextlib.contextmanager
def broken(control: str):
    """While inside, a ``mellum`` program that is traced is the broken one
    (``""`` changes nothing)."""
    import jax

    from cordum_tpu.models import mellum

    rotate, expert_layer = mellum.rotary.rotate, mellum.expert_layer
    rotation, walk, step = mellum.MellumConfig.rotation, mellum.paged_attention, mellum.ragged_step

    def rotate_fp8(x, ang, ratio=1.0):
        return jax.lax.reduce_precision(rotate(x, ang, ratio), 8, 3)

    def expert_layer_bf16(m, layer, cfg, live):
        return expert_layer(jax.lax.reduce_precision(m, 8, 7), layer, cfg, live)

    def rotation_plain(cfg, kind):
        return mellum.Rotation(theta=rotation(cfg, kind).theta)

    def walk_wrong_page(q, kp, vp, layer, tables, *rest, window=None):
        if window is not None:
            tables = tables.at[:, 5].set(tables[:, 6])
        return walk(q, kp, vp, layer, tables, *rest, window=window)

    def step_altered(params, kp, vp, wkp, wvp, tokens, positions, *rest, **kw):
        out, *arenas = step(params, kp, vp, wkp, wvp, tokens, positions, *rest, **kw)
        t = tokens.shape[0]
        nxt = jax.numpy.where(positions % 256 == 255, (out[:t] + 1) % rest[-1].vocab_size, out[:t])
        return (out.at[:t].set(nxt), *arenas)

    if control == "noyarn":
        mellum.MellumConfig.rotation = rotation_plain
    elif control == "ringpage":
        mellum.paged_attention = walk_wrong_page
    elif control == "token":
        mellum.ragged_step = step_altered
    elif control == "fp8":
        # ``mellum`` and ``axk1`` share the module: only a mellum program is traced here
        mellum.rotary.rotate = rotate_fp8
    elif control == "bf16":
        mellum.expert_layer = expert_layer_bf16
    elif control not in ("", "sound"):
        raise ValueError(control)
    try:
        yield
    finally:
        mellum.rotary.rotate, mellum.expert_layer = rotate, expert_layer
        mellum.MellumConfig.rotation, mellum.paged_attention, mellum.ragged_step = rotation, walk, step


@contextlib.contextmanager
def tails_said():
    """While inside, the harness's end-to-end arithmetic also prints the
    tails it does not report in this cell."""
    from benchmarks import run as bench_run
    from benchmarks.harness import stats

    end_to_end = stats.end_to_end

    def said(records, *, loop, t0, window_s):
        out = end_to_end(records, loop=loop, t0=t0, window_s=window_s)
        rows = sorted(((stats.tpot_s(r), r) for r in records if stats.tpot_s(r) is not None),
                      key=lambda x: -x[0])
        tpots = [1e3 * v for v, _ in rows]
        at = lambda r, k: round(r[k] - t0, 1)  # noqa: E731
        bench_run.say(
            phase="tails", ttft_p50_ms=out.get("ttft_p50_ms"), ttft_p95_ms=out.get("ttft_p95_ms"),
            tpot_ms={f"p{q}": round(stats.percentile(tpots, q), 2) for q in (50, 75, 90, 95, 99, 100)},
            slowest=[{"tpot_ms": round(1e3 * v, 1), "tokens": r["n_tokens"], "prompt": r["prompt_len"],
                      "first_s": at(r, "first"), "last_s": at(r, "last")} for v, r in rows[:8]],
            longest_prompts=[{"prompt": r["prompt_len"], "due_s": at(r, "due"), "first_s": at(r, "first")}
                             for r in sorted((r for r in records if r.get("first") is not None),
                                             key=lambda r: -r["prompt_len"])[:3]])
        return out

    stats.end_to_end = said
    try:
        yield
    finally:
        stats.end_to_end = end_to_end


def main() -> int:
    if len(sys.argv) < 2 or sys.argv[1] not in MODES:
        print(__doc__, file=sys.stderr)
        return 2
    mode = sys.argv.pop(1)
    from benchmarks import run as bench_run

    with broken(mode), tails_said() if mode == "sound" else contextlib.nullcontext():
        return bench_run.main()


if __name__ == "__main__":
    sys.exit(main())
