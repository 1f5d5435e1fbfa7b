"""Statebus replication invariants (ISSUE 8, docs/PROTOCOL.md §Replication):

* replica byte-for-byte KV equivalence after random op streams (incremental
  AND snapshot attach paths),
* sync-ack mode survives a primary kill with zero acked-commit loss,
* async mode bounds loss to the unacked replication window,
* promotion is exclusive (epoch fencing: a returning old primary demotes
  itself — no split-brain dual-accept),
* client failover: replica-set walk, resubscription, in-flight retransmit,
  reconnect metrics,
* AOF tail-corruption recovery (fuzz over random truncation points).
"""
from __future__ import annotations

import asyncio
import collections
import json
import os
import random
import subprocess
import sys
import time
from pathlib import Path

import msgpack
import pytest

from cordum_tpu.infra.chaos import ChaosProxy
from cordum_tpu.infra.kv import MemoryKV
from cordum_tpu.infra.metrics import Metrics
from cordum_tpu.infra.replication import parse_endpoint, parse_replica_set, probe_role
from cordum_tpu.infra.statebus import StateBusServer, StateBusConn, connect
from cordum_tpu.protocol import subjects as subj
from cordum_tpu.protocol.types import BusPacket, JobRequest


async def start_server(**kw) -> StateBusServer:
    srv = StateBusServer(port=0, **kw)
    await srv.start()
    return srv


async def start_replica(primary: StateBusServer, **kw) -> StateBusServer:
    return await start_server(
        replica_of=f"statebus://127.0.0.1:{primary.port}", **kw)


async def wait_for(cond, timeout_s: float = 10.0, msg: str = "condition"):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        v = cond()
        if asyncio.iscoroutine(v):
            v = await v
        if v:
            return
        await asyncio.sleep(0.01)
    raise AssertionError(f"timed out waiting for {msg}")


async def wait_caught_up(primary: StateBusServer, replica: StateBusServer,
                         timeout_s: float = 10.0) -> None:
    await wait_for(lambda: replica.repl.offset >= primary.repl.offset,
                   timeout_s, "replica catch-up")


def _rand_ops(rng: random.Random, n: int):
    """A reproducible random mutation stream over a small keyspace."""
    ops = []
    for i in range(n):
        k = f"k{rng.randrange(12)}"
        ops.append(rng.choice([
            ("set", k, f"v{i}".encode()),
            ("hset", f"h{rng.randrange(4)}", {f"f{rng.randrange(3)}": str(i).encode()}),
            ("zadd", f"z{rng.randrange(3)}", f"m{rng.randrange(6)}", float(i)),
            ("rpush", f"l{rng.randrange(3)}", str(i).encode()),
            ("sadd", f"s{rng.randrange(3)}", f"m{rng.randrange(6)}"),
            ("delete", k),
        ]))
    return ops


async def _apply_ops(kv, ops) -> None:
    for name, *args in ops:
        await getattr(kv, name)(*args)


def test_parse_replica_set():
    assert parse_endpoint("statebus://h:7520") == ("h", 7520)
    assert parse_endpoint("h:7520") == ("h", 7520)
    assert parse_replica_set(
        "statebus://a:7420|statebus://b:7520") == [("a", 7420), ("b", 7520)]
    assert parse_replica_set("statebus://a:7420") == [("a", 7420)]


async def test_replica_mirrors_random_op_stream():
    """Byte-for-byte equivalence: a replica attached from genesis mirrors a
    random op stream exactly — snapshots (values AND versions) identical."""
    primary = await start_server()
    replica = await start_replica(primary)
    kv, _, conn = await connect(f"statebus://127.0.0.1:{primary.port}")
    try:
        await wait_for(lambda: primary.repl.sessions, msg="replica attach")
        await _apply_ops(kv, _rand_ops(random.Random(8), 300))
        # pipes replicate as one atomic record
        ok, _ = await kv.pipe_execute({}, [("set", "pk", b"pv"),
                                           ("hset", "ph", {"f": b"1"})])
        assert ok
        await wait_caught_up(primary, replica)
        assert await primary.kv.snapshot() == await replica.kv.snapshot()
        assert replica.repl.epoch == primary.repl.epoch
    finally:
        await conn.close()
        await replica.stop()
        await primary.stop()


async def test_late_replica_reseeds_via_snapshot():
    """A replica too far behind the record backlog is re-seeded with a full
    snapshot — and still ends byte-for-byte identical."""
    primary = await start_server()
    primary.repl.backlog = collections.deque(maxlen=4)  # force snapshot path
    kv, _, conn = await connect(f"statebus://127.0.0.1:{primary.port}")
    replica = None
    try:
        await _apply_ops(kv, _rand_ops(random.Random(9), 120))
        replica = await start_replica(primary)
        await wait_for(lambda: replica._replica_link is not None
                       and replica._replica_link.connected.is_set(),
                       msg="replica link")
        assert replica._replica_link.last_sync_mode == "snapshot"
        await wait_caught_up(primary, replica)
        # post-snapshot stream continues incrementally
        await kv.set("after-snap", b"yes")
        await wait_caught_up(primary, replica)
        assert await primary.kv.snapshot() == await replica.kv.snapshot()
    finally:
        await conn.close()
        if replica is not None:
            await replica.stop()
        await primary.stop()


async def test_snapshot_preserves_versions():
    """Snapshot transfer keeps per-key versions, so watches held by clients
    that fail over to a freshly seeded replica stay valid."""
    src = MemoryKV()
    await src.set("a", b"1")
    await src.set("a", b"2")
    await src.set("a", b"3")
    await src.hset("h", {"f": b"x"})
    ver = await src.version("a")
    dst = MemoryKV()
    await dst.load_snapshot(await src.snapshot())
    assert await dst.get("a") == b"3"
    assert await dst.version("a") == ver
    assert await dst.commit({"a": ver}, [("set", "a", b"4")]) is True


async def test_replica_rejects_writes():
    primary = await start_server()
    replica = await start_replica(primary)
    kv, _, conn = await connect(f"statebus://127.0.0.1:{replica.port}")
    try:
        assert await kv.get("nope") is None  # reads serve
        with pytest.raises(RuntimeError, match="READONLY"):
            await kv.set("nope", b"1")
        with pytest.raises(RuntimeError, match="READONLY"):
            await kv.pipe_execute({}, [("set", "nope", b"1")])
    finally:
        await conn.close()
        await replica.stop()
        await primary.stop()


@pytest.mark.statebus
async def test_sync_mode_zero_acked_commit_loss_on_primary_crash():
    """The headline sync-ack invariant: every write the client saw `ok` for
    survives a primary SIGKILL-style crash and replica promotion."""
    primary = await start_server(sync_replication=True,
                                 heartbeat_interval_s=0.1,
                                 heartbeat_timeout_s=0.5)
    replica = await start_replica(primary, heartbeat_interval_s=0.1,
                                  heartbeat_timeout_s=0.5)
    url = (f"statebus://127.0.0.1:{primary.port}"
           f"|statebus://127.0.0.1:{replica.port}")
    kv, _, conn = await connect(url)
    acked: list[int] = []
    try:
        await wait_for(lambda: primary.repl.sessions, msg="replica attach")

        writing = True

        async def writer(i: int) -> None:
            while writing:  # a stream, not a burst: a fast machine acks 60
                # writes between two polls, and then nothing is mid-stream
                await kv.set(f"sync-{i}", str(i).encode(), )
                acked.append(i)
                i += 60

        # concurrent streams; crash the primary mid-stream
        tasks = [asyncio.ensure_future(writer(i)) for i in range(60)]
        await wait_for(lambda: len(acked) >= 10, msg="some acks")
        await primary.crash()
        # the failover walk retries the parked writes on the promoted
        # replica, so every writer eventually completes
        await wait_for(lambda: replica.role == "primary", msg="promotion")
        writing = False
        await asyncio.gather(*tasks)
        assert replica.role == "primary"
        for i in acked:
            assert await replica.kv.get(f"sync-{i}") == str(i).encode(), (
                f"acked commit sync-{i} lost across failover")
    finally:
        await conn.close()
        await replica.stop()
        await primary.stop()


async def test_async_mode_loss_bounded_to_unacked_window():
    """Async mode: a black-holed replication link bounds loss to EXACTLY the
    records committed after the link went dark — nothing before is lost,
    nothing after the promotion is half-applied."""
    primary = await start_server()
    proxy = ChaosProxy("127.0.0.1", primary.port)
    await proxy.start()
    replica = await start_server(
        replica_of=f"statebus://{proxy.listen_host}:{proxy.port}",
        heartbeat_interval_s=0.1, heartbeat_timeout_s=0.6)
    kv, _, conn = await connect(f"statebus://127.0.0.1:{primary.port}")
    try:
        await wait_for(lambda: primary.repl.sessions, msg="replica attach")
        for i in range(20):
            await kv.set(f"a-{i}", b"x")
        await wait_caught_up(primary, replica)
        replicated_offset = replica.repl.offset
        proxy.blackhole()
        for i in range(15):
            await kv.set(f"b-{i}", b"y")  # acked async; never replicated
        await primary.crash()
        await wait_for(lambda: replica.role == "primary", 5.0, "auto-promote")
        assert replica.repl.offset == replicated_offset
        for i in range(20):
            assert await replica.kv.get(f"a-{i}") == b"x"
        for i in range(15):
            assert await replica.kv.get(f"b-{i}") is None
    finally:
        await conn.close()
        await proxy.stop()
        await replica.stop()
        await primary.stop()


async def test_goaway_promotes_replica_immediately():
    """Graceful primary shutdown (SIGTERM path) broadcasts GOAWAY: the
    replica promotes NOW instead of waiting out the heartbeat timeout."""
    primary = await start_server(heartbeat_timeout_s=30.0)
    replica = await start_replica(primary, heartbeat_timeout_s=30.0)
    try:
        await wait_for(lambda: primary.repl.sessions, msg="replica attach")
        t0 = time.monotonic()
        await primary.stop()  # graceful: GOAWAY broadcast
        await wait_for(lambda: replica.role == "primary", 5.0, "goaway promote")
        assert time.monotonic() - t0 < 5.0  # nowhere near the 30s heartbeat
        text = replica.metrics.render()
        assert 'reason="primary-goaway"' in text
    finally:
        await replica.stop()
        await primary.stop()


async def test_admin_promote_and_role_frames():
    primary = await start_server()
    replica = await start_replica(primary)
    kv, _, conn = await connect(f"statebus://127.0.0.1:{replica.port}")
    try:
        await wait_for(lambda: primary.repl.sessions, msg="replica attach")
        doc = await probe_role("127.0.0.1", primary.port)
        assert doc["role"] == "primary" and doc["replicas"]
        doc = await conn.call("role")
        assert doc["role"] == "replica"
        doc = await conn.call("promote")
        assert doc["role"] == "primary" and doc["epoch"] == 1
        await kv.set("now-writable", b"1")  # writes accepted post-promotion
        assert await kv.get("now-writable") == b"1"
    finally:
        await conn.close()
        await replica.stop()
        await primary.stop()


@pytest.mark.statebus
async def test_promotion_is_exclusive_old_primary_demotes():
    """Epoch fencing: a promoted replica bumps + persists its epoch; the old
    primary returning finds a live higher-epoch primary in its peer set,
    demotes itself to replica, and re-syncs — no dual-accept."""
    primary = await start_server(heartbeat_interval_s=0.1,
                                 heartbeat_timeout_s=0.5)
    replica = await start_replica(primary, heartbeat_interval_s=0.1,
                                  heartbeat_timeout_s=0.5)
    kv, _, conn = await connect(f"statebus://127.0.0.1:{primary.port}")
    old_port = primary.port
    try:
        await wait_for(lambda: primary.repl.sessions, msg="replica attach")
        await kv.set("pre-crash", b"1")
        await wait_caught_up(primary, replica)
        await conn.close()
        await primary.crash()
        await wait_for(lambda: replica.role == "primary", 5.0, "auto-promote")
        assert replica.repl.epoch == 1
        # old primary returns on its old port, with the replica in its peer
        # set: the startup probe finds the higher epoch and demotes it
        returned = StateBusServer(
            port=old_port,
            peers=(f"statebus://127.0.0.1:{old_port}",
                   f"statebus://127.0.0.1:{replica.port}"))
        await returned.start()
        await wait_for(lambda: returned.role == "replica", 5.0, "self-demotion")
        assert returned.replica_of.endswith(str(replica.port))
        # exactly one writable node: the returned server rejects writes...
        kv2, _, conn2 = await connect(f"statebus://127.0.0.1:{old_port}")
        with pytest.raises(RuntimeError, match="READONLY"):
            await kv2.set("split-brain", b"!")
        await conn2.close()
        # ...and mirrors the new primary's stream
        kv3, _, conn3 = await connect(f"statebus://127.0.0.1:{replica.port}")
        await kv3.set("post-promotion", b"2")
        await wait_caught_up(replica, returned)
        assert await returned.kv.get("post-promotion") == b"2"
        assert await returned.kv.get("pre-crash") == b"1"
        assert returned.repl.epoch == replica.repl.epoch
        await conn3.close()
        await returned.stop()
    finally:
        await replica.stop()
        await primary.stop()


async def test_client_failover_resubscribes_and_counts_reconnects():
    """StateBusConn walks the replica set on primary loss, re-issues every
    subscription, and counts the failover in
    cordum_statebus_reconnects_total{reason}."""
    primary = await start_server(heartbeat_interval_s=0.1,
                                 heartbeat_timeout_s=0.4)
    replica = await start_replica(primary, heartbeat_interval_s=0.1,
                                  heartbeat_timeout_s=0.4)
    url = (f"statebus://127.0.0.1:{primary.port}"
           f"|statebus://127.0.0.1:{replica.port}")
    kv, bus, conn = await connect(url)
    m = Metrics()
    kv.bind_metrics(m)
    got: list[str] = []
    try:
        async def h(s, p):
            got.append(p.job_request.job_id)

        await bus.subscribe("sys.job.submit", h, queue="g")
        await bus.publish(subj.SUBMIT,
                          BusPacket.wrap(JobRequest(job_id="before", topic="t")))
        await wait_for(lambda: got == ["before"], msg="pre-failover delivery")
        await primary.crash()
        await wait_for(lambda: replica.role == "primary", 5.0, "auto-promote")
        await bus.publish(subj.SUBMIT,
                          BusPacket.wrap(JobRequest(job_id="after", topic="t")))
        await wait_for(lambda: got == ["before", "after"], 10.0,
                       "post-failover delivery via re-issued subscription")
        assert conn.reconnect_count >= 1
        assert m.statebus_reconnects.total() >= 1
        assert (conn.host, conn.port) == ("127.0.0.1", replica.port)
    finally:
        await conn.close()
        await replica.stop()
        await primary.stop()


async def test_parked_call_retransmits_across_server_restart():
    """A call issued while the server is down parks its frame and completes
    after reconnect — pipelined commits are never silently dropped."""
    from cordum_tpu.infra.chaos import free_port

    port = free_port()
    srv = StateBusServer(port=port)
    await srv.start()
    kv, _, conn = await connect(f"statebus://127.0.0.1:{port}")
    try:
        await kv.set("warm", b"1")
        await srv.crash()
        task = asyncio.ensure_future(kv.set("parked", b"2"))
        await asyncio.sleep(0.1)
        assert not task.done()
        srv2 = StateBusServer(port=port)
        await srv2.start()
        await asyncio.wait_for(task, 10)
        assert await kv.get("parked") == b"2"
        await srv2.stop()
    finally:
        await conn.close()
        await srv.stop()


async def test_sync_ack_timeout_degrades_not_blocks():
    """A replica that stops acking degrades sync→async after the sync
    timeout (counted) instead of holding the partition hostage."""
    primary = await start_server(sync_replication=True)
    primary.repl.sync_timeout_s = 0.3
    proxy = ChaosProxy("127.0.0.1", primary.port)
    await proxy.start()
    replica = await start_server(
        replica_of=f"statebus://{proxy.listen_host}:{proxy.port}",
        heartbeat_timeout_s=30.0, auto_promote=False)
    kv, _, conn = await connect(f"statebus://127.0.0.1:{primary.port}")
    try:
        await wait_for(lambda: primary.repl.sessions, msg="replica attach")
        await kv.set("synced", b"1")  # replica live: fast ack
        proxy.blackhole()
        t0 = time.monotonic()
        await kv.set("degraded", b"2")
        assert time.monotonic() - t0 >= 0.25
        assert primary.metrics.statebus_sync_ack_timeouts.total() == 1
        assert await kv.get("degraded") == b"2"
    finally:
        await conn.close()
        await proxy.stop()
        await replica.stop()
        await primary.stop()


async def test_spuriously_failed_over_primary_demotes_at_runtime():
    """The OTHER split-brain direction: a primary that never died but whose
    replica promoted anyway (a stall read as primary-dead) finds the
    higher-epoch primary at its next peer probe and demotes itself —
    WITHOUT a restart, so dual-accept is bounded by the probe interval."""
    primary = await start_server(heartbeat_interval_s=0.05,
                                 heartbeat_timeout_s=0.2)
    replica = await start_replica(primary, heartbeat_interval_s=0.05,
                                  heartbeat_timeout_s=30.0)
    primary.peers = (f"statebus://127.0.0.1:{primary.port}",
                     f"statebus://127.0.0.1:{replica.port}")
    kv, _, conn = await connect(f"statebus://127.0.0.1:{primary.port}")
    try:
        await wait_for(lambda: primary.repl.sessions, msg="replica attach")
        await kv.set("pre-split", b"1")
        await wait_caught_up(primary, replica)
        # spurious promotion: the replica is promoted while the primary is
        # alive and healthy — two primaries exist for a moment
        await replica.promote(reason="admin")
        assert primary.role == "primary" and replica.role == "primary"
        await wait_for(lambda: primary.role == "replica", 10.0,
                       "runtime self-demotion")
        # epoch adoption rides the re-sync handshake, just after the flip
        await wait_for(lambda: primary.repl.epoch == 1, 10.0, "epoch adoption")
        assert replica.repl.epoch == 1
        # exactly one writable node again, and the demoted server mirrors it
        kv2, _, conn2 = await connect(f"statebus://127.0.0.1:{replica.port}")
        await kv2.set("post-split", b"2")
        await wait_caught_up(replica, primary)
        assert await primary.kv.get("post-split") == b"2"
        await conn2.close()
    finally:
        await conn.close()
        await replica.stop()
        await primary.stop()


@pytest.mark.statebus
async def test_cli_statebus_status_and_promote():
    """`cordumctl statebus status` renders per-partition role/offset/lag
    straight from the fleet; `statebus promote` drives the admin frame."""
    primary = await start_server()
    replica = await start_replica(primary)
    url = (f"statebus://127.0.0.1:{primary.port}"
           f"|statebus://127.0.0.1:{replica.port}")

    def run_cli(*args: str) -> subprocess.CompletedProcess:
        return subprocess.run(
            [sys.executable, "-m", "cordum_tpu.cli", *args],
            capture_output=True, text=True, timeout=60,
            cwd=str(Path(__file__).resolve().parents[1]),
            env={**os.environ, "JAX_PLATFORMS": "cpu"})

    try:
        await wait_for(lambda: primary.repl.sessions, msg="replica attach")
        out = await asyncio.to_thread(run_cli, "statebus", "status",
                                      "--url", url, "--json")
        assert out.returncode == 0, out.stderr
        rows = json.loads(out.stdout)
        assert [r["role"] for r in rows] == ["primary", "replica"]
        assert rows[0]["replicas"] == 1 and rows[0]["partition"] == 0
        out = await asyncio.to_thread(
            run_cli, "statebus", "promote",
            f"statebus://127.0.0.1:{replica.port}")
        assert out.returncode == 0, out.stderr
        doc = json.loads(out.stdout)
        assert doc["role"] == "primary" and doc["epoch"] == 1
        assert replica.role == "primary"
        # the table renderer also holds together (no --json)
        out = await asyncio.to_thread(run_cli, "statebus", "status", "--url", url)
        assert out.returncode == 0 and "endpoint" in out.stdout
    finally:
        await replica.stop()
        await primary.stop()


# ---------------------------------------------------------------------------
# AOF tail-corruption recovery (crash mid-write)
# ---------------------------------------------------------------------------


async def _complete_prefix_state(blob: bytes) -> tuple[int, dict]:
    """Oracle: apply every COMPLETE well-formed record in `blob` to a fresh
    MemoryKV (mirroring replay semantics) and return (n_records, k→v)."""
    unpacker = msgpack.Unpacker(raw=False, strict_map_key=False)
    unpacker.feed(blob)
    kv = MemoryKV()
    n = 0
    while True:
        try:
            entry = unpacker.unpack()
        except msgpack.OutOfData:
            break
        except Exception:  # noqa: BLE001 - garbage tail is the point
            break
        if (not isinstance(entry, (list, tuple)) or not entry
                or not isinstance(entry[0], str)):
            break
        op, args = entry[0], entry[1:]
        if op == "pipe_execute":
            await kv.pipe_execute(*args)
        elif op not in ("repl_meta", "repl_snapshot"):
            await getattr(kv, op)(*args)
        n += 1
    out = {}
    for k in await kv.keys():
        out[k] = await kv.get(k)
    return n, out


@pytest.mark.statebus
async def test_aof_tail_corruption_fuzz(tmp_path):
    """Replay of an AOF truncated at ANY byte (or with a garbage tail)
    recovers to the last complete record — never raises, and appends
    continue from a clean tail afterwards."""
    aof = str(tmp_path / "full.aof")
    srv = await start_server(aof_path=aof)
    kv, _, conn = await connect(f"statebus://127.0.0.1:{srv.port}")
    for i in range(50):
        await kv.set(f"fz-{i}", str(i).encode())
    ok, _ = await kv.pipe_execute({}, [("set", "fz-pipe", b"p"),
                                       ("zadd", "fz-z", "m", 1.0)])
    assert ok
    await conn.close()
    await srv.stop()
    blob = await asyncio.to_thread(_read, aof)
    rng = random.Random(17)
    cuts = sorted(rng.randrange(1, len(blob)) for _ in range(8))
    for case, cut in enumerate([*cuts, None]):  # None = garbage-append case
        path = str(tmp_path / f"cut-{case}.aof")
        data = blob[:cut] if cut is not None else blob + b"\xc1\x00garbage"
        await asyncio.to_thread(_write, path, data)
        expect_n, expect_state = await _complete_prefix_state(data)
        srv2 = await start_server(aof_path=path)
        try:
            got = {k: await srv2.kv.get(k) for k in await srv2.kv.keys()}
            assert got == expect_state, f"cut at {cut}: state diverged"
            assert srv2.repl.offset == expect_n
            # the tail was truncated clean: appends + another replay work
            kv2, _, conn2 = await connect(f"statebus://127.0.0.1:{srv2.port}")
            await kv2.set("post-recovery", b"ok")
            await conn2.close()
        finally:
            await srv2.stop()
        srv3 = await start_server(aof_path=path)
        try:
            assert await srv3.kv.get("post-recovery") == b"ok"
        finally:
            await srv3.stop()


def _read(path: str) -> bytes:
    with open(path, "rb") as f:
        return f.read()


def _write(path: str, data: bytes) -> None:
    with open(path, "wb") as f:
        f.write(data)
