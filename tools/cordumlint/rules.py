"""The six cordum-tpu rules.  Each encodes an invariant this control plane
depends on; the docstrings carry the rationale shown in ``--list-rules``.
"""
from __future__ import annotations

import ast
import re
from typing import Iterator

from .core import Finding, LintContext, Rule

# ---------------------------------------------------------------------------
# CL001
# ---------------------------------------------------------------------------

_DEADLINE_WORDS = re.compile(
    r"timeout|deadline|ttl|lease|expir|cutoff|stale|breaker|window|elapsed"
    r"|backoff|retry|renew|interval|latency|heartbeat",
    re.IGNORECASE,
)


class NoWallClockDeadline(Rule):
    """CL001: wall-clock ``time.time()`` in timeout/lease/TTL/deadline
    arithmetic.  NTP steps and clock skew make wall time go backwards;
    lease math built on it either never expires or expires instantly.
    Use ``time.monotonic()`` for in-process durations, or the blessed
    ``cordum_tpu.utils.ids.now_us/now_ms`` helpers when comparing against
    persisted cross-process timestamps (the job store's clock)."""

    id = "CL001"
    name = "no-wall-clock-deadline"
    description = (
        "time.time() forbidden in timeout/lease/TTL arithmetic; use "
        "time.monotonic() or utils.ids.now_us/now_ms"
    )
    # utils/ids.py IS the blessed wall-clock source for persisted timestamps
    default_allow_paths = ("cordum_tpu/utils/ids.py", "*/utils/ids.py")

    # modules whose whole purpose is deadline/lease arithmetic: every
    # wall-clock call there is a violation, keyword context or not
    default_strict_paths = (
        "cordum_tpu/controlplane/scheduler/reconciler.py",
        "cordum_tpu/controlplane/scheduler/safety_client.py",
        "cordum_tpu/infra/registry.py",
        "cordum_tpu/infra/locks.py",
        "cordum_tpu/infra/kv.py",
    )

    def _is_wall_clock_call(self, node: ast.Call) -> bool:
        fn = node.func
        if isinstance(fn, ast.Attribute) and fn.attr in ("time", "time_ns"):
            return isinstance(fn.value, ast.Name) and fn.value.id == "time"
        return False

    def check(self, ctx: LintContext) -> Iterator[Finding]:
        strict = ctx.rel_path in tuple(
            self.options.get("strict_paths", self.default_strict_paths)
        )
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Call) and self._is_wall_clock_call(node)):
                continue
            stmt_text = ctx.statement_text(node)
            if strict or _DEADLINE_WORDS.search(stmt_text):
                yield self.finding(
                    ctx,
                    node,
                    "wall-clock time.time() in deadline/lease/timeout "
                    "arithmetic; use time.monotonic() for in-process "
                    "durations or utils.ids.now_us/now_ms for persisted "
                    "timestamps",
                )


# ---------------------------------------------------------------------------
# CL002
# ---------------------------------------------------------------------------

_BROAD_NAMES = {"Exception", "BaseException"}


class NoSilentSwallow(Rule):
    """CL002: broad ``except`` whose body neither logs, re-raises, nor
    returns a fallback value.  This is the ``bench.py`` class of bug: a
    crashed JAX child reported a partial metric as if healthy.  In a
    fail-closed control plane a swallowed error IS a wrong answer."""

    id = "CL002"
    name = "no-silent-swallow"
    description = (
        "broad `except Exception` with a pass/continue/bare-return body; "
        "log, re-raise, or return an explicit fallback"
    )

    def _is_broad(self, handler: ast.ExceptHandler) -> bool:
        t = handler.type
        if t is None:
            return True  # bare except:
        names = []
        if isinstance(t, ast.Tuple):
            names = [e.id for e in t.elts if isinstance(e, ast.Name)]
        elif isinstance(t, ast.Name):
            names = [t.id]
        return any(n in _BROAD_NAMES for n in names)

    def _is_silent_stmt(self, stmt: ast.stmt) -> bool:
        if isinstance(stmt, (ast.Pass, ast.Continue, ast.Break)):
            return True
        if isinstance(stmt, ast.Return):
            return stmt.value is None or (
                isinstance(stmt.value, ast.Constant) and stmt.value.value is None
            )
        if isinstance(stmt, ast.Expr) and isinstance(stmt.value, ast.Constant):
            return True  # docstring / ellipsis
        return False

    def check(self, ctx: LintContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            if not self._is_broad(node):
                continue
            if all(self._is_silent_stmt(s) for s in node.body):
                yield self.finding(
                    ctx,
                    node,
                    "broad except swallows the error silently; log it with "
                    "context, re-raise, or return an explicit fallback "
                    "(narrow to the exceptions you actually expect)",
                )


# ---------------------------------------------------------------------------
# CL003
# ---------------------------------------------------------------------------

_BLOCKING_ATTR_CALLS = {
    ("time", "sleep"): "await asyncio.sleep(...)",
    ("requests", "get"): "aiohttp (or asyncio.to_thread)",
    ("requests", "post"): "aiohttp (or asyncio.to_thread)",
    ("requests", "put"): "aiohttp (or asyncio.to_thread)",
    ("requests", "delete"): "aiohttp (or asyncio.to_thread)",
    ("requests", "request"): "aiohttp (or asyncio.to_thread)",
    ("urllib.request", "urlopen"): "aiohttp (or asyncio.to_thread)",
    ("subprocess", "run"): "asyncio.create_subprocess_exec",
    ("subprocess", "call"): "asyncio.create_subprocess_exec",
    ("subprocess", "check_call"): "asyncio.create_subprocess_exec",
    ("subprocess", "check_output"): "asyncio.create_subprocess_exec",
    ("socket", "create_connection"): "asyncio.open_connection",
}


class NoBlockingInAsync(Rule):
    """CL003: blocking calls (``time.sleep``, sync HTTP, ``subprocess``,
    ``open``) inside ``async def`` bodies.  One blocked event loop stalls
    every job the service is carrying — at 1k scheduled jobs/sec a 100 ms
    sync read is 100 dropped scheduling slots."""

    id = "CL003"
    name = "no-blocking-in-async"
    description = (
        "time.sleep / sync HTTP / blocking file IO inside async def; use "
        "asyncio.sleep, aiohttp, or asyncio.to_thread"
    )

    def _async_owner(self, ctx: LintContext, node: ast.AST):
        """The async function whose *runtime* body contains node (stops at
        the nearest enclosing def — nested sync helpers run out-of-line)."""
        for anc in ctx.ancestors(node):
            if isinstance(anc, ast.FunctionDef):
                return None
            if isinstance(anc, ast.AsyncFunctionDef):
                return anc
        return None

    def check(self, ctx: LintContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            owner = self._async_owner(ctx, node)
            if owner is None:
                continue
            hint = self._blocking_hint(node)
            if hint:
                yield self.finding(
                    ctx,
                    node,
                    f"blocking call in async def {owner.name}(); use {hint}",
                )

    def _blocking_hint(self, node: ast.Call) -> str:
        fn = node.func
        if isinstance(fn, ast.Name) and fn.id == "open":
            return "asyncio.to_thread(...) or load outside the event loop"
        if isinstance(fn, ast.Attribute) and isinstance(fn.value, ast.Name):
            return _BLOCKING_ATTR_CALLS.get((fn.value.id, fn.attr), "")
        if isinstance(fn, ast.Attribute) and isinstance(fn.value, ast.Attribute):
            base = fn.value
            if isinstance(base.value, ast.Name):
                dotted = f"{base.value.id}.{base.attr}"
                return _BLOCKING_ATTR_CALLS.get((dotted, fn.attr), "")
        return ""


# ---------------------------------------------------------------------------
# CL004
# ---------------------------------------------------------------------------

_JOB_STATES = {
    "PENDING", "APPROVAL_REQUIRED", "SCHEDULED", "DISPATCHED", "RUNNING",
    "SUCCEEDED", "FAILED", "CANCELLED", "TIMEOUT", "DENIED",
}


class StateTransitionDiscipline(Rule):
    """CL004: raw string writes to a job ``state`` field outside the
    transition table's home.  Every state change must flow through
    ``JobStore.set_state`` (which validates against
    ``protocol.types.ALLOWED_TRANSITIONS``) — a raw write can resurrect a
    terminal job or skip the approval gate."""

    id = "CL004"
    name = "state-transition-discipline"
    description = (
        "job state assignments outside protocol/types.py / infra/jobstore.py "
        "must use JobStore.set_state, not raw string writes"
    )
    default_allow_paths = (
        "cordum_tpu/protocol/types.py",
        "cordum_tpu/infra/jobstore.py",
    )

    def _is_state_target(self, target: ast.expr) -> bool:
        if isinstance(target, ast.Attribute) and target.attr == "state":
            return True
        if isinstance(target, ast.Subscript):
            sl = target.slice
            return isinstance(sl, ast.Constant) and sl.value == "state"
        return False

    def _is_raw_state_value(self, value: ast.expr) -> bool:
        return isinstance(value, ast.Constant) and value.value in _JOB_STATES

    def check(self, ctx: LintContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            targets: list[ast.expr] = []
            value: ast.expr | None = None
            if isinstance(node, ast.Assign):
                targets, value = node.targets, node.value
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                targets, value = [node.target], node.value
            elif isinstance(node, ast.Dict):
                for k, v in zip(node.keys, node.values):
                    if (
                        isinstance(k, ast.Constant)
                        and k.value == "state"
                        and self._is_raw_state_value(v)
                    ):
                        yield self.finding(
                            ctx, v,
                            "raw job-state string literal; pass a JobState "
                            "member so the transition table stays the single "
                            "source of truth",
                        )
                continue
            if value is None or not self._is_raw_state_value(value):
                continue
            for t in targets:
                if self._is_state_target(t):
                    yield self.finding(
                        ctx, node,
                        "raw job-state write bypasses the legal-transition "
                        "table; use JobStore.set_state(job_id, JobState.X)",
                    )


# ---------------------------------------------------------------------------
# CL005
# ---------------------------------------------------------------------------

_BUS_METHODS = {"publish", "subscribe", "request", "publish_wait", "unsubscribe"}
_SUBJECT_PREFIXES = ("sys.", "worker.", "job.")


class SubjectLiterals(Rule):
    """CL005: ad-hoc bus subject strings.  Subjects are wire protocol: a
    typo'd literal routes jobs nowhere (silently, with an at-least-once bus
    redelivering into the void).  They must come from
    ``protocol/subjects.py`` constants or its ``direct_subject()`` helper."""

    id = "CL005"
    name = "subject-literals"
    description = (
        "bus subjects must come from protocol/subjects.py constants, not "
        "ad-hoc string literals / f-strings"
    )
    default_allow_paths = ("cordum_tpu/protocol/subjects.py",)

    def _literal_subject(self, arg: ast.expr) -> bool:
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            return arg.value.startswith(_SUBJECT_PREFIXES)
        if isinstance(arg, ast.JoinedStr):
            head = arg.values[0] if arg.values else None
            return (
                isinstance(head, ast.Constant)
                and isinstance(head.value, str)
                and head.value.startswith(_SUBJECT_PREFIXES)
            )
        return False

    def check(self, ctx: LintContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call):
                fn = node.func
                if (
                    isinstance(fn, ast.Attribute)
                    and fn.attr in _BUS_METHODS
                    and node.args
                    and self._literal_subject(node.args[0])
                ):
                    yield self.finding(
                        ctx, node.args[0],
                        "ad-hoc subject literal in bus call; use a "
                        "protocol.subjects constant (or direct_subject())",
                    )
            elif isinstance(node, ast.JoinedStr):
                # f"worker.{id}.jobs" built anywhere = re-implemented router
                parts = [
                    v.value for v in node.values
                    if isinstance(v, ast.Constant) and isinstance(v.value, str)
                ]
                if parts and parts[0].startswith("worker.") and any(
                    p.endswith(".jobs") for p in parts
                ):
                    yield self.finding(
                        ctx, node,
                        "hand-built worker subject f-string; use "
                        "protocol.subjects.direct_subject(worker_id)",
                    )


# ---------------------------------------------------------------------------
# CL007
# ---------------------------------------------------------------------------

_JSON_CODEC_FNS = {"dumps", "loads", "dump", "load"}


class NoJsonOnHotPath(Rule):
    """CL007: ``json.dumps``/``json.loads`` in scheduler hot-path modules.
    The wire and the stored records are msgpack (ISSUE 6 moved the last
    JSON codecs off the jobstore hot path — a measurable slice of the 1×1
    regression); a JSON call creeping back in silently re-taxes every job.
    Contract JSON (worker env vars) and legacy-read fallbacks live in
    ``infra/codec.py``, which is the one place allowed to import json."""

    id = "CL007"
    name = "no-json-on-hot-path"
    description = (
        "json.dumps/json.loads forbidden in hot-path modules "
        "(infra/jobstore.py, infra/kv.py, infra/statebus.py, "
        "scheduler/engine.py); use infra/codec.py pack_record/unpack_record "
        "or its env-contract helpers"
    )

    # the rule fires ONLY in these modules (inverse of allow_paths)
    default_hot_paths = (
        "cordum_tpu/infra/jobstore.py",
        "cordum_tpu/infra/kv.py",
        "cordum_tpu/infra/statebus.py",
        "cordum_tpu/controlplane/scheduler/engine.py",
    )

    def check(self, ctx: LintContext) -> Iterator[Finding]:
        hot = tuple(self.options.get("hot_paths", self.default_hot_paths))
        if ctx.rel_path not in hot:
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            if (
                isinstance(fn, ast.Attribute)
                and fn.attr in _JSON_CODEC_FNS
                and isinstance(fn.value, ast.Name)
                and fn.value.id == "json"
            ):
                yield self.finding(
                    ctx, node,
                    f"json.{fn.attr} on the scheduler hot path; use the "
                    "msgpack codec (infra/codec.py pack_record/unpack_record) "
                    "or, for env-contract JSON, its dumps_env_json/"
                    "loads_env_json helpers",
                )


from .program_rules import PROGRAM_RULES  # noqa: E402 - registry lives here

RULES: tuple[type[Rule], ...] = (
    NoWallClockDeadline,
    NoSilentSwallow,
    NoBlockingInAsync,
    StateTransitionDiscipline,
    SubjectLiterals,
    NoJsonOnHotPath,
) + PROGRAM_RULES
