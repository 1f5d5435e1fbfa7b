#!/usr/bin/env python
"""One run of one benchmark cell.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process that takes the chip, makes seeded weights, composes gateway ->
scheduler -> bus -> worker -> ServingEngine in-process, warms the cell's
shapes through the served path, then lets a child process (the load
generator, off jax) offer the cell's traffic over HTTP for ``--seconds``.
After a bounded drain it checks the outputs against the plain float32
reference and prints ONE JSON object as the last line of stdout: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``).  ``--trace 0`` reports the end-to-end metrics, ``--trace 1``
the per-layer metrics.  Without a TPU it exits 2 and prints no result.

Builder's options, never passed by the driver: ``--rehearse`` (whatever
platform jax finds, tiny widths, same control flow; its output names the
device and no number of it is a device number), ``--rate`` (override an
open-loop rate, for the knee sweep), ``--control 1`` (also read the
lower-precision control on the sampled requests).
"""
from __future__ import annotations

import time

T_START = time.monotonic()  # process start, as near as Python can stamp it

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

from benchmarks.harness import cells, reference, stats, traffic  # noqa: E402

DRAIN_S = 90.0
#: rehearsal widths (``LlamaConfig.tiny()``'s); pool shapes and context stay the cell's
TINY = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256}


def say(**doc) -> None:
    print(json.dumps(doc), flush=True)


class NoChip(Exception):
    pass


def take_device(cell, rehearse: bool):
    """First touch of jax: place the compile cache, count compiles, name the
    device as jax reports it.  A measured run needs a TPU and the cell's chips."""
    import jax

    from benchmarks.harness.stack import CompileLog
    from cordum_tpu.parallel.mesh import configure_compile_cache

    cache_dir = configure_compile_cache()
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if not rehearse and (dev["platform"] != "tpu" or dev["count"] < cell.chips):
        raise NoChip(f"cell {cell.name} needs {cell.chips} TPU chip(s); jax found {dev}")
    return dev, cache_dir, CompileLog()


class Collected:
    """What one run gathers for the per-layer readers (see README.md)."""

    def __init__(self) -> None:
        self.spans: list[dict] = []
        #: on_step: (monotonic, [(n, start, sample, draft)], distinct pages behind the rows)
        self.steps: list[tuple] = []
        self.step_calls: list[tuple[float, float]] = []  # traced run: backend.step (start, end)
        self.step_seconds: list[float] = []
        self.ttft_seconds: list[float] = []


async def poll_rings(serving, got: Collected, stop: asyncio.Event) -> None:
    """``ServingStats`` keeps capped rings; copy what each half second adds."""
    st = serving.stats
    last_steps, last_ttft = st.steps, len(st.ttft_seconds)
    while True:
        stopping = stop.is_set()
        d = st.steps - last_steps
        if d > 0:
            got.step_seconds.extend(list(st.step_seconds)[-d:])
            last_steps += d
        n = len(st.ttft_seconds)
        if n > last_ttft:
            got.ttft_seconds.extend(list(st.ttft_seconds)[last_ttft:])
            last_ttft = n
        if stopping:
            return
        try:
            await asyncio.wait_for(stop.wait(), timeout=0.5)
        except asyncio.TimeoutError:
            pass


async def child_call(proc, cmd: dict, timeout_s: float) -> dict:
    proc.stdin.write((json.dumps(cmd) + "\n").encode())
    await proc.stdin.drain()
    line = await asyncio.wait_for(proc.stdout.readline(), timeout=timeout_s)
    if not line:
        raise RuntimeError(f"the load generator exited ({proc.returncode})")
    return json.loads(line)


async def profile_slice(start_at: float, length_s: float, trace_dir: str, out: dict) -> None:
    """Trace a steady slice of the window; the python tracer stays off (it
    would dominate the host)."""
    import jax

    await asyncio.sleep(max(0.0, start_at - time.monotonic()))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    loop = asyncio.get_running_loop()
    await loop.run_in_executor(None, lambda: jax.profiler.start_trace(
        trace_dir, profiler_options=opts))
    out["t0"] = time.monotonic()
    await asyncio.sleep(length_s)
    out["t1"] = time.monotonic()
    await loop.run_in_executor(None, jax.profiler.stop_trace)
    out["stopped"] = time.monotonic()


async def watch_window(serving, got: Collected, t0: float, t_close: float, out: dict) -> None:
    """Read the engine's counters when the window opens and when it closes
    (a closed loop's ramp runs before it, the drain after it), and copy the
    rings' window part in between."""
    import jax

    st = serving.stats
    keys = ("steps", "occupancy_sum", "prefill_tokens", "decoded_tokens", "prefix_hits",
            "drafted_tokens", "accepted_tokens", "admission_waits", "admitted", "failed")
    await asyncio.sleep(max(0.0, t0 - time.monotonic()))
    out["before"] = {k: getattr(st, k) for k in keys}
    stop = asyncio.Event()
    poller = asyncio.ensure_future(poll_rings(serving, got, stop))
    try:
        await asyncio.sleep(max(0.0, t_close - time.monotonic()))
    finally:
        stop.set()
        await poller
    out["after"] = {k: getattr(st, k) for k in keys}
    out["memory"] = jax.devices()[0].memory_stats() or {}


async def run_cell(args, cell) -> dict:
    import jax

    marks = {"imports": time.monotonic() - T_START}
    dev, cache_dir, compiles = take_device(cell, args.rehearse)
    marks["device"] = time.monotonic() - T_START
    doc = dict(cell.config)
    if args.rehearse:
        doc.update(TINY)
    pool = doc["pool"]
    fam = cell.family
    cfg = fam.program_config(doc)
    tr = dict(cell.traffic)
    if args.rate:
        tr["rate_rps"] = args.rate
    ramp_s = float(tr.get("ramp_s", 0.0)) if tr["loop"] == "closed" else 0.0
    requests = traffic.generate(
        tr, seed=args.seed, seconds=args.seconds + ramp_s, vocab=doc["vocab_size"],
        context=doc["max_position_embeddings"], max_new_cap=pool["max_new_tokens"])
    say(phase="start", workload=cell.name, seed=args.seed, seconds=args.seconds,
        trace=args.trace, device=dev, cache_dir=cache_dir, requests=len(requests),
        loop=tr["loop"], rate_rps=tr.get("rate_rps"), clients=tr.get("clients"), ramp_s=ramp_s)

    from benchmarks.harness.stack import API_KEY, Stack

    t = time.monotonic()
    params = jax.block_until_ready(fam.make_params(doc, args.seed))
    t_weights = time.monotonic() - t
    stack = Stack(fam, cfg, params, pool, args.seed)
    got = Collected()
    be = stack.backend
    if be.on_step is None:  # the gang uses it; no one-chip cell does
        def on_step(entries):
            got.steps.append((time.monotonic(),
                              [(len(e.tokens), e.start, bool(e.sample), e.draft) for e in entries],
                              len({p for e in entries for p in e.pages})))
        be.on_step = on_step
    if args.trace:
        from cordum_tpu.protocol import subjects as subj

        async def on_span(subject, pkt):
            sp = pkt.span
            if sp is not None and sp.trace_id:
                got.spans.append({"name": sp.name, "trace": sp.trace_id, "start_us": sp.start_us,
                                  "end_us": sp.end_us, "service": sp.service,
                                  "at": time.monotonic()})
        inner = be.step

        def timed_step(entries):
            t_in = time.monotonic()
            try:
                return inner(entries)
            finally:
                got.step_calls.append((t_in, time.monotonic()))
        be.step = timed_step

    proc = None
    trace_dir = ""
    watcher = None
    marks["weights"] = time.monotonic() - T_START
    await stack.start()
    try:
        marks["stack"] = time.monotonic() - T_START
        if args.trace:
            await stack.bus.subscribe(subj.TRACE_SPAN, on_span)
        await stack.wait_registered()
        proc = await asyncio.create_subprocess_exec(
            sys.executable, os.path.join(REPO_ROOT, "benchmarks", "harness", "loadgen.py"),
            stack.api, API_KEY, stdin=asyncio.subprocess.PIPE, stdout=asyncio.subprocess.PIPE,
            limit=2 ** 30, env={**os.environ, "JAX_PLATFORMS": "cpu"})
        ready = json.loads(await asyncio.wait_for(proc.stdout.readline(), timeout=60))
        if not ready.get("ready"):
            raise RuntimeError(f"the load generator did not come up: {ready}")

        # warm-up through the served path: two short sessions at once, each
        # longer than one prefill chunk, so the one ragged program has run
        # mixed prefill and decode rows before the window opens
        warm_len = min(pool["prefill_budget"] + pool["page_size"] + 3,
                       doc["max_position_embeddings"] - 8)
        warm = traffic.generate(
            {"loop": "closed", "clients": 2, "block": 2, "pool_rps": 0.001,
             "prompt_tokens": {"dist": "fixed", "value": warm_len, "min": 1, "max": warm_len},
             "new_tokens": {"dist": "fixed", "value": 6, "min": 1, "max": 6}},
            seed=args.seed + 1, seconds=1.0, vocab=doc["vocab_size"],
            context=doc["max_position_embeddings"], max_new_cap=pool["max_new_tokens"])
        t = time.monotonic()
        w = await child_call(proc, {"cmd": "run", "requests": warm, "loop": "closed",
                                    "clients": 2, "t0": time.monotonic(), "window_s": 0.2,
                                    "drain_s": 1100.0, "tag": "w"}, timeout_s=1150)
        t_warm = time.monotonic() - t
        if not all(r.get("state") == "SUCCEEDED" for r in w["records"]):
            raise RuntimeError(f"warm-up did not succeed: {w['records']}")

        # ---- the window (a closed loop's ramp before it counts as set-up) ----
        t0 = time.monotonic() + 0.25 + ramp_s
        t_close = t0 + args.seconds
        setup_s = t0 - T_START
        say(phase="setup", setup_s=setup_s, reached_s=marks, weights_s=t_weights, warmup_s=t_warm,
            ramp_s=ramp_s, compile_s=compiles.seconds(), compile_requests=len(compiles.events),
            cache_hits=compiles.cache_hits)
        seen: dict = {}
        watcher = asyncio.ensure_future(watch_window(stack.serving, got, t0, t_close, seen))
        slice_info: dict = {}
        profiler = None
        if args.trace:
            trace_dir = tempfile.mkdtemp(prefix="cordum-bench-trace-")
            profiler = asyncio.ensure_future(profile_slice(
                t0 + 0.45 * args.seconds, min(3.0, 0.25 * args.seconds), trace_dir, slice_info))
        answer = await child_call(proc, {
            "cmd": "run", "requests": requests, "loop": tr["loop"],
            "clients": tr.get("clients", 0), "t0": t0, "window_s": args.seconds,
            "ramp_s": ramp_s, "drain_s": DRAIN_S, "tag": "r"},
            timeout_s=ramp_s + args.seconds + DRAIN_S + 60)
        if profiler is not None:
            await profiler
        await watcher
        before, after, mem = seen["before"], seen["after"], seen["memory"]
        window_compiles = compiles.between(t0, t_close)
    finally:
        if watcher is not None and not watcher.done():
            watcher.cancel()
        if proc is not None:
            if proc.returncode is None:
                try:
                    proc.stdin.write(b'{"cmd": "quit"}\n')
                    await proc.stdin.drain()
                    await asyncio.wait_for(proc.wait(), timeout=10)
                except (asyncio.TimeoutError, ConnectionError, BrokenPipeError):
                    proc.kill()
                    await proc.wait()
        await stack.stop()

    records = answer["records"]
    loop_kind = tr["loop"]
    e2e = stats.end_to_end(records, loop=loop_kind, t0=t0, window_s=args.seconds)
    half = t0 + args.seconds / 2

    def in_flight(at: float) -> int:
        return sum(1 for r in records if r["due"] <= at and (r["done"] is None or r["done"] > at))
    unfinished = [r["i"] for r in records if r.get("state") != "SUCCEEDED"]
    faults = reference.stream_faults(records)
    late = [r["sent"] - r["due"] for r in records]
    attempted = sum(1 for r in records if not r.get("ramp"))
    say(phase="window", attempted=attempted, sent_in_ramp=len(records) - attempted,
        unfinished=len(unfinished),
        tokens_per_s_by_half=[stats.tokens_in_window(records, a, a + args.seconds / 2)
                              / (args.seconds / 2) for a in (t0, half)],
        ttft_samples=e2e["ttft_samples"], tpot_samples=e2e["tpot_samples"],
        client_ttft_p50_ms=e2e.get("ttft_p50_ms"),  # printed in every cell, judged only where listed
        gen_late_p95_ms=1e3 * stats.percentile(late, 95) if late else None,
        drained_s=answer["drained_s"], steps=after["steps"] - before["steps"],
        # where a stall was, if there was one: the child's own worst oversleep, and the
        # three longest step cycles (on_step to on_step) with their offsets into the window
        gen_loop_lag_max_ms=1e3 * answer["loop_lag_max_s"], gen_loop_lag_at_s=answer["loop_lag_at_s"],
        longest_cycles_ms_at_s=longest_cycles(got.steps, t0, t_close),
        step_wall_max_ms=1e3 * max(got.step_seconds, default=0.0),
        arrivals_per_s=attempted / args.seconds,
        completions_per_s=sum(1 for r in records if r["done"] is not None
                              and t0 <= r["done"] < t_close) / args.seconds,
        in_flight_at_half=in_flight(half), in_flight_at_close=in_flight(t_close),
        engine={k: after[k] - before[k] for k in before},
        queue_exhausted=answer["queue_exhausted"], stream_faults=faults[:5],
        window_compiles=window_compiles, child_imported_jax=answer["jax_imported"],
        errors=[r["error"] for r in records if r.get("error")][:5])

    # ---- the check, after the program's state is freed --------------------
    stack.free_device_state()
    t = time.monotonic()
    chk = doc["check"]
    sample = reference.pick_sample(records, args.seed, chk["sample_tokens"], chk["sample_requests"])
    ref = fam.reference.Reference(doc, doc["max_position_embeddings"])
    numbers = reference.gaps_of(ref, params, sample) if sample else {
        "gap_mean": float("nan"), "gap_max": float("nan"), "tokens": 0, "requests": 0}
    numbers.update(stream_faults=len(faults), unfinished=len(unfinished),
                   window_compiles=len(window_compiles))
    limits = {"gap_mean": chk["gap_mean_limit"], "gap_max": chk["gap_max_limit"],
              "stream_faults": 0, "unfinished": 0, "window_compiles": 0}
    ok, rows = reference.verdict(numbers, limits)
    if answer["queue_exhausted"] or answer["tap_closed"] or answer["jax_imported"]:
        ok = False
    if not args.rehearse and dev["platform"] != "tpu":
        ok = False
    check_doc = {"phase": "check", "compared": rows, "sampled_requests": numbers["requests"],
                 "sampled_tokens": numbers["tokens"],
                 "longest": max((r["prompt_len"] + r["n_tokens"] for r in sample), default=0),
                 "mismatch_share": numbers.get("mismatch_share")}
    if args.control and sample:
        ctl = reference.gaps_of(ref, params, sample, control=True)
        c_ok, c_rows = reference.verdict({**numbers, **ctl}, limits)
        check_doc["control"] = {"correct": c_ok, "compared": c_rows[:2],
                                "mismatch_share": ctl["mismatch_share"]}
    check_doc["reference_s"] = time.monotonic() - t
    say(**check_doc)

    device = {**dev, "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}
    result: dict = {"correct": bool(ok), "attempted": attempted, "failed": len(unfinished)}
    if not args.trace:
        values = {**e2e, "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                             for m in cell.end_to_end if m["name"] in values}
    else:
        from benchmarks.harness import trace_reduce
        from benchmarks.harness.peaks import peaks_for

        reduced = trace_reduce.reduce_file(trace_reduce.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        window_steps = [s for s in got.steps if t0 <= s[0] < t_close]
        run = {
            "config": doc, "pool": pool, "traffic": tr, "loop": loop_kind, "records": records,
            "t0": t0, "window_s": args.seconds,
            "spans": [s for s in got.spans if t0 <= s["at"] < t_close],
            "stats_delta": {k: after[k] - before[k] for k in before},
            "step_seconds": got.step_seconds, "ttft_seconds": got.ttft_seconds,
            "steps": window_steps, "step_calls": got.step_calls, "slice": slice_info,
            "trace": reduced, "memory": mem,
            "peaks": None if args.rehearse else peaks_for(dev["kind"]),
            "max_batch_tokens": be.max_batch_tokens, "max_sessions": be.max_seqs,
        }
        metrics = {}
        for m in cell.per_layer:
            value = cell.readers[m["name"]].read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        if reduced.get("devices"):
            # busy time and window from the same events, on the device's clock: the
            # trace runs from start_trace to the end of stop_trace, which is longer
            # than the host's sleep between them
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["span_s"]
            result["breakdown"] = breakdown(reduced, got, slice_info)
        say(phase="trace", devices=reduced.get("devices"), busy_s=reduced.get("busy_s"),
            span_s=reduced.get("span_s"), slice_s=slice_info["t1"] - slice_info["t0"],
            stop_trace_s=slice_info["stopped"] - slice_info["t1"],
            modules={k: len(v) for k, v in (reduced.get("module_runs_s") or {}).items()},
            spans=len(got.spans))
    result["device"] = device
    return result


def longest_cycles(steps: list, t0: float, t_close: float, n: int = 3) -> list:
    """The ``n`` longest intervals between consecutive ``on_step`` calls of
    the window, as [milliseconds, seconds into the window]."""
    at = [s[0] for s in steps if t0 <= s[0] < t_close]
    cycles = sorted(((b - a, a - t0) for a, b in zip(at, at[1:])), reverse=True)[:n]
    return [[1e3 * d, off] for d, off in cycles]


def breakdown(reduced: dict, got: Collected, sl: dict) -> dict:
    """The device operations that took most of the traced span, and its idle
    time.  The trace says where the device idled (between programs, inside
    them); the host's clock says what the host was doing between programs, as
    far as the harness sees from outside: the mean of a step cycle's two host
    parts over the slice, times the program executions the trace holds."""
    runs = [d for k, v in reduced["module_runs_s"].items() if "ragged" in k for d in v]
    gaps = [["device idle between programs (trace, sum)", sum(reduced["between_modules_s"])],
            ["device idle inside programs, between their operations (trace, sum)",
             reduced["inside_modules_idle_s"]]]
    calls = [(a, b) for a, b in got.step_calls if sl["t0"] <= a and b <= sl["t1"]]
    if len(calls) > 1 and runs:
        n = len(runs)
        between = [calls[i + 1][0] - calls[i][1] for i in range(len(calls) - 1)]
        in_call = sum(b - a for a, b in calls) / len(calls) - sum(runs) / n
        gaps += [["host between backend.step calls: engine emits, publishes, assembles "
                  "(host clock, mean x executions)", n * sum(between) / len(between)],
                 ["host inside backend.step, ragged program not running: host arrays, "
                  "transfer, result copy (host clock, mean x executions)", n * in_call]]
    gaps += [[f"longest single gap between programs #{i + 1} (trace)", g]
             for i, g in enumerate(reduced["between_modules_s"][:5])]
    return {"device_ops": reduced["device_ops"][:10], "idle_gaps": gaps[:10]}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--rate", type=float, default=0.0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    try:
        cell = cells.resolve(args.workload)
        result = asyncio.run(run_cell(args, cell))
    except NoChip as e:
        print(f"no chip: {e}", file=sys.stderr)
        return 2
    except Exception:  # noqa: BLE001 - the one catch: report on stderr, no result line
        traceback.print_exc()
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
