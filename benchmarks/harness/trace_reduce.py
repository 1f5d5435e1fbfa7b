"""From a profiler trace (``.xplane.pb``) to numbers, with nothing but
``jax.profiler.ProfileData``.  The reduction is the benchmark's: every PR
computes busy time, program durations and the heaviest operations the same
way.

A TPU trace has one plane per chip (``/device:TPU:<n>``) with lines among
which ``XLA Modules`` holds one event per execution of a compiled program
(named ``jit_<fn>(<hash>)``) and ``XLA Ops`` one event per operation.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Iterable

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def union_ns(intervals: Iterable[tuple[float, float]]) -> float:
    """Total length of the union of [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals: Iterable[tuple[float, float]]) -> list[tuple[float, float]]:
    """The idle stretches between the merged intervals, as (start, length)."""
    out, cur_e = [], None
    for s, e in sorted(intervals):
        if cur_e is not None and s > cur_e:
            out.append((cur_e, s - cur_e))
        cur_e = e if cur_e is None else max(cur_e, e)
    return out


def op_kind(name: str) -> str:
    """An operation event is named by its whole HLO line, ``%fusion.12 =
    bf16[64,4096]{1,0:T(8,128)} fusion(...)``; reduce it to the instruction's
    name without its number and the element type and dimensions of its result
    (``fusion bf16[64,4096]``), so that the 32 copies of one operation in 16
    layers add up under one name."""
    head, sep, rest = name.partition(" = ")
    base = re.sub(r"\.\d+$", "", head.strip().lstrip("%"))
    if not sep:
        return base
    shape = re.match(r"\(?\s*([a-z0-9]+\[[0-9,]*\])", rest)
    return f"{base} {shape.group(1)}" if shape else base


def module_base(name: str) -> str:
    """``jit_ragged_program(1234567)`` -> ``jit_ragged_program``."""
    return name.split("(", 1)[0]


def reduce_planes(planes: list[dict]) -> dict:
    """``planes``: [{"name", "lines": [{"name", "events": [(name, start_ns,
    duration_ns)]}]}] -> the reduced trace.  Busy time is the union of the
    operation intervals of a device, averaged over the devices that ran
    anything."""
    devices = []
    for plane in planes:
        if not DEVICE_PLANE.match(plane["name"]):
            continue
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        ops = lines.get(OPS_LINE) or []
        mods = lines.get(MODULE_LINE) or []
        if not ops and not mods:
            continue
        # an op interval for busy time; where a trace has no op line, the
        # module executions stand in (coarser: gaps inside a program vanish)
        busy_src = ops or mods
        ivals = [(s, s + d) for _, s, d in busy_src if d > 0]
        op_time: dict[str, float] = {}
        for name, _, d in ops:
            kind = op_kind(name)
            op_time[kind] = op_time.get(kind, 0.0) + d
        runs: dict[str, list[float]] = {}
        for name, _, d in mods:
            runs.setdefault(module_base(name), []).append(d)
        mod_ivals = sorted((s, s + d) for _, s, d in mods)
        devices.append({
            "plane": plane["name"], "busy_ns": union_ns(ivals),
            "first_ns": min(s for s, _ in ivals), "last_ns": max(e for _, e in ivals),
            "op_time_ns": op_time, "module_runs_ns": runs,
            "gaps_between_modules_ns": gaps_ns(mod_ivals),
            "gaps_inside_modules_ns": max(0.0, sum(d for _, d in gaps_ns(ivals)) - sum(
                d for _, d in gaps_ns(mod_ivals))) if ops and mods else 0.0,
        })
    if not devices:
        return {"devices": 0}
    n = len(devices)
    op_total: dict[str, float] = {}
    runs_total: dict[str, list[float]] = {}
    for dev in devices:
        for k, v in dev["op_time_ns"].items():
            op_total[k] = op_total.get(k, 0.0) + v / n
        for k, v in dev["module_runs_ns"].items():
            runs_total.setdefault(k, []).extend(v)
    top_ops = sorted(op_total.items(), key=lambda kv: -kv[1])[:10]
    return {
        "devices": n,
        "busy_s": sum(d["busy_ns"] for d in devices) / n / 1e9,
        "span_s": max(d["last_ns"] - d["first_ns"] for d in devices) / 1e9,
        "device_ops": [[name, ns / 1e9] for name, ns in top_ops],
        "module_runs_s": {k: [x / 1e9 for x in v] for k, v in runs_total.items()},
        "between_modules_s": sorted(
            (d / 1e9 for dev in devices for _, d in dev["gaps_between_modules_ns"]), reverse=True),
        "inside_modules_idle_s": sum(d["gaps_inside_modules_ns"] for d in devices) / n / 1e9,
    }


def load_planes(path: str) -> list[dict]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        planes.append({"name": plane.name, "lines": [
            {"name": ln.name,
             "events": [(ev.name, float(ev.start_ns), float(ev.duration_ns)) for ev in ln.events]}
            for ln in plane.lines if ln.name in (OPS_LINE, MODULE_LINE)]})
    return planes


def reduce_file(path: str) -> dict:
    return reduce_planes(load_planes(path))
