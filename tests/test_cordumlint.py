"""cordumlint: each rule fires exactly where expected (bad fixture), stays
quiet on the idiomatic fix (good fixture); suppression + baseline mechanics."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from tools.cordumlint import baseline as baseline_mod
from tools.cordumlint.cli import main as cli_main
from tools.cordumlint.core import lint_paths


def run_lint(tmp_path: Path, name: str, source: str, **kw):
    f = tmp_path / name
    f.parent.mkdir(parents=True, exist_ok=True)
    f.write_text(source)
    result = lint_paths([name], root=tmp_path, **kw)
    return result.findings


def rule_ids(findings):
    return [f.rule_id for f in findings]


# ---------------------------------------------------------------- CL001

CL001_BAD = """\
import time

def expire(ttl_s):
    deadline = time.time() + ttl_s
    return deadline
"""

CL001_GOOD = """\
import time

def expire(ttl_s):
    deadline = time.monotonic() + ttl_s
    return deadline
"""


def test_cl001_fires_on_wall_clock_deadline(tmp_path):
    findings = run_lint(tmp_path, "a.py", CL001_BAD, select={"CL001"})
    assert rule_ids(findings) == ["CL001"]
    assert findings[0].line == 4


def test_cl001_quiet_on_monotonic(tmp_path):
    assert run_lint(tmp_path, "a.py", CL001_GOOD, select={"CL001"}) == []


def test_cl001_quiet_without_deadline_context(tmp_path):
    src = "import time\nstamp = time.time()\n"
    assert run_lint(tmp_path, "a.py", src, select={"CL001"}) == []


def test_cl001_strict_path_needs_no_keyword(tmp_path):
    src = "import time\nx = time.time()\n"
    findings = run_lint(
        tmp_path, "cordum_tpu/infra/locks.py", src, select={"CL001"}
    )
    assert rule_ids(findings) == ["CL001"]


def test_cl001_allows_blessed_clock_module(tmp_path):
    src = "import time\n\ndef now_with_ttl(ttl):\n    return time.time() + ttl\n"
    assert run_lint(tmp_path, "cordum_tpu/utils/ids.py", src, select={"CL001"}) == []


# ---------------------------------------------------------------- CL002

CL002_BAD = """\
def f():
    try:
        risky()
    except Exception:
        pass
"""

CL002_BAD_TUPLE = """\
async def stop(task):
    try:
        await task
    except (CancelledError, Exception):
        pass
"""

CL002_GOOD = """\
import logging

def f():
    try:
        risky()
    except Exception as e:
        logging.getLogger("x").error("risky failed: %s", e)
"""

CL002_GOOD_FALLBACK = """\
def f():
    try:
        return risky()
    except Exception:
        return 0.0, 0.0
"""


def test_cl002_fires_on_silent_pass(tmp_path):
    findings = run_lint(tmp_path, "a.py", CL002_BAD, select={"CL002"})
    assert rule_ids(findings) == ["CL002"]


def test_cl002_fires_on_tuple_with_exception(tmp_path):
    findings = run_lint(tmp_path, "a.py", CL002_BAD_TUPLE, select={"CL002"})
    assert rule_ids(findings) == ["CL002"]


def test_cl002_fires_on_bare_except(tmp_path):
    src = "try:\n    x()\nexcept:\n    pass\n"
    assert rule_ids(run_lint(tmp_path, "a.py", src, select={"CL002"})) == ["CL002"]


def test_cl002_quiet_when_logged_or_fallback(tmp_path):
    assert run_lint(tmp_path, "a.py", CL002_GOOD, select={"CL002"}) == []
    assert run_lint(tmp_path, "b.py", CL002_GOOD_FALLBACK, select={"CL002"}) == []


def test_cl002_quiet_on_narrow_except(tmp_path):
    src = "try:\n    x()\nexcept KeyError:\n    pass\n"
    assert run_lint(tmp_path, "a.py", src, select={"CL002"}) == []


# ---------------------------------------------------------------- CL003

CL003_BAD = """\
import time

async def handler():
    time.sleep(1.0)
"""

CL003_BAD_OPEN = """\
async def load(path):
    with open(path) as f:
        return f.read()
"""

CL003_GOOD = """\
import asyncio

async def handler():
    await asyncio.sleep(1.0)

async def load(path):
    return await asyncio.to_thread(_read, path)

def _read(path):
    with open(path) as f:
        return f.read()
"""


def test_cl003_fires_on_sleep_and_open(tmp_path):
    assert rule_ids(run_lint(tmp_path, "a.py", CL003_BAD, select={"CL003"})) == ["CL003"]
    assert rule_ids(run_lint(tmp_path, "b.py", CL003_BAD_OPEN, select={"CL003"})) == ["CL003"]


def test_cl003_quiet_on_async_idioms(tmp_path):
    assert run_lint(tmp_path, "a.py", CL003_GOOD, select={"CL003"}) == []


def test_cl003_ignores_nested_sync_helper(tmp_path):
    src = """\
async def outer():
    def helper(path):
        with open(path) as f:
            return f.read()
    return helper
"""
    assert run_lint(tmp_path, "a.py", src, select={"CL003"}) == []


# ---------------------------------------------------------------- CL004

CL004_BAD = """\
def resurrect(job):
    job.state = "RUNNING"
"""

CL004_BAD_DICT = """\
def payload(job_id):
    return {"job_id": job_id, "state": "PENDING"}
"""

CL004_GOOD = """\
from cordum_tpu.protocol.types import JobState

def payload(job_id):
    return {"job_id": job_id, "state": JobState.PENDING.value}

async def advance(store, job_id):
    await store.set_state(job_id, JobState.RUNNING)
"""


def test_cl004_fires_on_raw_state_writes(tmp_path):
    assert rule_ids(run_lint(tmp_path, "a.py", CL004_BAD, select={"CL004"})) == ["CL004"]
    assert rule_ids(run_lint(tmp_path, "b.py", CL004_BAD_DICT, select={"CL004"})) == ["CL004"]


def test_cl004_quiet_on_enum_usage(tmp_path):
    assert run_lint(tmp_path, "a.py", CL004_GOOD, select={"CL004"}) == []


def test_cl004_allows_transition_table_home(tmp_path):
    findings = run_lint(
        tmp_path, "cordum_tpu/infra/jobstore.py", CL004_BAD, select={"CL004"}
    )
    assert findings == []


def test_cl004_ignores_non_state_strings(tmp_path):
    src = 'def f(x):\n    x.state = "closed"\n'  # circuit breaker, not a JobState
    assert run_lint(tmp_path, "a.py", src, select={"CL004"}) == []


# ---------------------------------------------------------------- CL005

CL005_BAD = """\
async def tap(bus, handler):
    await bus.subscribe("sys.job.result", handler)
"""

CL005_BAD_FSTRING = """\
def subject_for(worker_id):
    return f"worker.{worker_id}.jobs"
"""

CL005_GOOD = """\
from cordum_tpu.protocol import subjects as subj

async def tap(bus, handler):
    await bus.subscribe(subj.RESULT, handler)

def subject_for(worker_id):
    return subj.direct_subject(worker_id)
"""


def test_cl005_fires_on_subject_literals(tmp_path):
    assert rule_ids(run_lint(tmp_path, "a.py", CL005_BAD, select={"CL005"})) == ["CL005"]
    assert rule_ids(run_lint(tmp_path, "b.py", CL005_BAD_FSTRING, select={"CL005"})) == ["CL005"]


def test_cl005_quiet_on_constants(tmp_path):
    assert run_lint(tmp_path, "a.py", CL005_GOOD, select={"CL005"}) == []


def test_cl005_allows_subjects_module(tmp_path):
    src = 'SUBMIT = "sys.job.submit"\n\ndef direct_subject(w):\n    return f"worker.{w}.jobs"\n'
    assert run_lint(
        tmp_path, "cordum_tpu/protocol/subjects.py", src, select={"CL005"}
    ) == []


# ---------------------------------------------------------------- CL007

CL007_BAD = """\
import json

def put(kv, rec):
    return json.dumps(rec).encode()

def get(b):
    return json.loads(b)
"""

CL007_GOOD = """\
from cordum_tpu.infra.codec import pack_record, unpack_record

def put(kv, rec):
    return pack_record(rec)

def get(b):
    return unpack_record(b)
"""


def test_cl007_fires_in_hot_path_module(tmp_path):
    findings = run_lint(
        tmp_path, "cordum_tpu/infra/jobstore.py", CL007_BAD, select={"CL007"}
    )
    assert rule_ids(findings) == ["CL007", "CL007"]
    assert "msgpack codec" in findings[0].message


def test_cl007_fires_in_every_declared_hot_module(tmp_path):
    for mod in (
        "cordum_tpu/infra/kv.py",
        "cordum_tpu/infra/statebus.py",
        "cordum_tpu/controlplane/scheduler/engine.py",
    ):
        findings = run_lint(tmp_path, mod, CL007_BAD, select={"CL007"})
        assert rule_ids(findings) == ["CL007", "CL007"], mod


def test_cl007_quiet_on_msgpack_codec(tmp_path):
    assert run_lint(
        tmp_path, "cordum_tpu/infra/jobstore.py", CL007_GOOD, select={"CL007"}
    ) == []


def test_cl007_quiet_outside_hot_paths(tmp_path):
    # codec.py (the legacy-JSON fallback home) and arbitrary modules may
    # use json freely — the rule is scoped to the declared hot modules
    assert run_lint(
        tmp_path, "cordum_tpu/infra/codec.py", CL007_BAD, select={"CL007"}
    ) == []
    assert run_lint(tmp_path, "cordum_tpu/cli.py", CL007_BAD, select={"CL007"}) == []


def test_cl007_suppressible_inline(tmp_path):
    src = (
        "import json\n"
        "def put(rec):\n"
        "    return json.dumps(rec)  "
        "# cordumlint: disable=CL007 -- legacy export path\n"
    )
    assert run_lint(
        tmp_path, "cordum_tpu/infra/jobstore.py", src, select={"CL007"}
    ) == []


# ---------------------------------------------------------------- engine

def test_inline_suppression(tmp_path):
    src = """\
def f():
    try:
        risky()
    except Exception:  # cordumlint: disable=CL002 -- crash loop guard, metrics count it
        pass
"""
    assert run_lint(tmp_path, "a.py", src, select={"CL002"}) == []


def test_inline_suppression_standalone_line(tmp_path):
    src = """\
import time

def lease(ttl):
    # cordumlint: disable=CL001 -- cross-host lease, wall clock is the contract
    return time.time() + ttl
"""
    assert run_lint(tmp_path, "a.py", src, select={"CL001"}) == []


def test_suppression_is_per_rule(tmp_path):
    src = """\
import time

async def f(ttl):
    time.sleep(ttl)  # cordumlint: disable=CL001
"""
    # CL001 disabled but CL003 still fires on the same line
    findings = run_lint(tmp_path, "a.py", src)
    assert rule_ids(findings) == ["CL003"]


def test_rule_disable_via_config(tmp_path):
    config = {"rules": {"CL002": {"enabled": False}}}
    assert run_lint(tmp_path, "a.py", CL002_BAD, config=config) == []


def test_multiple_rules_one_file(tmp_path):
    src = CL001_BAD + "\n" + CL002_BAD
    findings = run_lint(tmp_path, "a.py", src)
    assert sorted(set(rule_ids(findings))) == ["CL001", "CL002"]


# ---------------------------------------------------------------- baseline

def test_baseline_suppresses_grandfathered_only(tmp_path):
    f = tmp_path / "a.py"
    f.write_text(CL002_BAD)
    result = lint_paths(["a.py"], root=tmp_path)
    bl = tmp_path / "baseline.json"
    n = baseline_mod.write(bl, result.findings, "legacy handler, tracked in #42")
    assert n == 1

    # same finding → baselined
    doc = baseline_mod.load(bl)
    marked = baseline_mod.apply(result.findings, doc)
    assert all(fi.baselined for fi in marked)

    # a NEW violation elsewhere is not covered
    f.write_text(CL002_BAD + "\n\n" + CL002_BAD.replace("risky()", "other()"))
    result2 = lint_paths(["a.py"], root=tmp_path)
    marked2 = baseline_mod.apply(result2.findings, doc)
    assert [m.baselined for m in marked2] == [True, False]


def test_baseline_survives_line_shift(tmp_path):
    f = tmp_path / "a.py"
    f.write_text(CL002_BAD)
    result = lint_paths(["a.py"], root=tmp_path)
    bl = tmp_path / "baseline.json"
    baseline_mod.write(bl, result.findings, "grandfathered")
    # unrelated code above shifts the finding down 3 lines
    f.write_text("X = 1\nY = 2\nZ = 3\n" + CL002_BAD)
    shifted = lint_paths(["a.py"], root=tmp_path)
    marked = baseline_mod.apply(shifted.findings, baseline_mod.load(bl))
    assert [m.baselined for m in marked] == [True]


def test_baseline_invalidates_when_line_changes(tmp_path):
    f = tmp_path / "a.py"
    f.write_text(CL002_BAD)
    result = lint_paths(["a.py"], root=tmp_path)
    bl = tmp_path / "baseline.json"
    baseline_mod.write(bl, result.findings, "grandfathered")
    # the offending handler itself changes → must be re-decided
    f.write_text(CL002_BAD.replace("except Exception:", "except (ValueError, Exception):"))
    changed = lint_paths(["a.py"], root=tmp_path)
    marked = baseline_mod.apply(changed.findings, baseline_mod.load(bl))
    assert [m.baselined for m in marked] == [False]


# ---------------------------------------------------------------- CLI

def test_cli_exit_codes_and_json(tmp_path, capsys):
    (tmp_path / "clean.py").write_text("x = 1\n")
    assert cli_main(["clean.py", "--root", str(tmp_path)]) == 0

    (tmp_path / "dirty.py").write_text(CL002_BAD)
    assert cli_main(["dirty.py", "--root", str(tmp_path)]) == 1

    capsys.readouterr()
    rc = cli_main(["dirty.py", "--root", str(tmp_path), "--format", "json"])
    assert rc == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["summary"] == {"CL002": 1}
    assert doc["findings"][0]["rule_id"] == "CL002"


def test_cli_write_baseline_requires_justification(tmp_path, capsys):
    (tmp_path / "dirty.py").write_text(CL002_BAD)
    assert cli_main(["dirty.py", "--root", str(tmp_path), "--write-baseline"]) == 2

    rc = cli_main([
        "dirty.py", "--root", str(tmp_path), "--write-baseline",
        "--justification", "legacy, tracked",
    ])
    assert rc == 0
    capsys.readouterr()
    # baselined finding no longer fails the gate
    assert cli_main(["dirty.py", "--root", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "(1 baselined)" in out


def test_cli_select_and_list_rules(tmp_path, capsys):
    (tmp_path / "a.py").write_text(CL001_BAD + "\n" + CL002_BAD)
    rc = cli_main(["a.py", "--root", str(tmp_path), "--select", "CL001"])
    assert rc == 1
    out = capsys.readouterr().out
    assert "CL001" in out and "CL002" not in out

    assert cli_main(["--list-rules", "--root", str(tmp_path)]) == 0
    listing = capsys.readouterr().out
    for rid in ("CL001", "CL002", "CL003", "CL004", "CL005", "CL007"):
        assert rid in listing


def test_repo_tree_is_clean():
    """The gate the CI enforces: the shipped tree has zero active findings."""
    repo = Path(__file__).resolve().parents[1]
    rc = cli_main(["cordum_tpu", "bench.py", "--root", str(repo)])
    assert rc == 0
