"""chip_smoke.py's contract, as far as a host with no chip can show it: the
rehearsal runs the whole script at tiny sizes on the CPU, the default
invocation refuses to run without a chip, and only the worker binary loads
jax."""
import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def run_smoke(*args, xla_flags=None, timeout=420):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    if xla_flags is not None:
        env["XLA_FLAGS"] = xla_flags
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=timeout, cwd=str(REPO), env=env)
    lines = proc.stdout.strip().splitlines()
    return proc, json.loads(lines[-1]), [json.loads(ln) for ln in lines if ln.startswith("{")]


def test_rehearse_runs_both_phases_on_cpu():
    proc, last, docs = run_smoke("--rehearse")
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    assert last["ok"] is True and last["device"]["platform"] == "cpu", last
    assert set(last) == {"ok", "device"} and set(last["device"]) == {"platform", "kind", "count"}
    phases = {d.get("phase"): d for d in docs}
    assert phases["A"]["worker_drain_exit"] == 0 and phases["A"]["children"] >= 5
    b = phases["B"]
    assert b["serving"]["compiled_programs"] == 1
    assert b["reference"]["compared"] > 0 and b["serving"]["cow_copies"] >= 1
    assert b["hibernate"]["restored_pages"] >= 1 and b["serving"]["drafted_tokens"] > 0


def test_rehearse_four_virtual_devices_runs_only_the_tp_phase():
    proc, last, docs = run_smoke(
        "--rehearse", "--chips", "4",
        xla_flags="--xla_force_host_platform_device_count=4")
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 4}}
    assert [d["phase"] for d in docs if "phase" in d] == ["start", "tp4", "done"]
    tp = docs[1]
    assert all(0.24 <= s <= 0.27 for s in tp["shares"]["weights"]["per_device_share"])
    assert tp["collectives"]["all-reduce"] >= 2 * tp["reduced"]["n_layers"]


def test_default_invocation_fails_without_a_chip():
    proc, last, _ = run_smoke()
    assert proc.returncode != 0
    assert last["ok"] is False and "device" not in last, last
    assert "not a TPU" in last["error"], last
    assert '"ok": true' not in proc.stdout


def test_only_the_worker_binary_loads_jax():
    code = (
        "import sys\n"
        "import cordum_tpu.cmd.statebus, cordum_tpu.cmd.safety_kernel\n"
        "import cordum_tpu.cmd.scheduler, cordum_tpu.cmd.gateway\n"
        "import cordum_tpu.cmd.workflow_engine, cordum_tpu.sdk.client\n"
        "import tools.platform_smoke\n"
        "assert 'jax' not in sys.modules, 'a control-plane binary imported jax'\n"
    )
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120, cwd=str(REPO),
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
