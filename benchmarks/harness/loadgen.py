"""The load generator: a child process that never imports jax and speaks
HTTP to the gateway through the SDK (``Client.submit_job`` for the request,
the gateway's ``/api/v1/stream`` WebSocket tap for the tokens, the SDK's
``merge_stream_packet`` for assembly, ``Client.job_status`` for the
terminal result).  Every end-to-end number is taken here, on the client's
side of the served path, on this process's clock.

It holds ONE tap for all its streams.  The tap carries every job's packets
to every subscriber, so ``Client.generate`` (one tap per request) would
cost N x N deliveries at N concurrent streams; a client with many streams
reads one tap and sorts by job id, as here.

Protocol: one JSON line per command on stdin, one JSON line per answer on
stdout.  ``{"cmd": "run", "requests": [...], "loop": "open"|"closed",
"clients": n, "t0": <time.monotonic() at which offset 0 is due>,
"window_s": s, "drain_s": s, "ramp_s": s}`` sends the requests and answers with
one record per request that was due (or sent) inside the window; a closed loop
with a ramp starts its clients ``ramp_s`` before ``t0``, so that the window
opens on a system already in its steady state, and marks what it sent before
``t0`` with ``"ramp": true``; ``{"cmd": "quit"}`` ends the process.  ``time.monotonic()`` is the machine's clock,
shared with the parent.
"""
from __future__ import annotations

import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

TOPIC = "job.tpu.generate"


class Run:
    def __init__(self, client, cmd: dict, tag: str) -> None:
        self.client = client
        self.cmd = cmd
        self.tag = tag
        self.loop_kind = cmd["loop"]
        self.t0 = float(cmd["t0"])
        self.t_first = self.t0 - float(cmd.get("ramp_s", 0.0))  # closed loop: clients start
        self.t_close = self.t0 + float(cmd["window_s"])
        self.t_drain = self.t_close + float(cmd.get("drain_s", 0.0))
        self.requests = cmd["requests"]
        self.recs: dict[str, dict] = {}  # job_id -> record
        self.followers: dict[int, list[dict]] = {}
        for r in self.requests:
            if "after" in r:
                self.followers.setdefault(r["after"], []).append(r)
        self.tasks: set[asyncio.Task] = set()
        self.clients: list[asyncio.Future] = []  # closed loop
        self.queue_exhausted = False

    # -- the tap ----------------------------------------------------------
    def on_packet(self, pkt: dict) -> None:
        body = pkt.get("payload") or {}
        rec = self.recs.get(body.get("job_id"))
        if rec is None:
            return
        now = time.monotonic()
        kind = pkt.get("kind")
        if kind == "job_progress" and body.get("status_hint") == "stream":
            from cordum_tpu.sdk.client import merge_stream_packet

            toks = body.get("tokens") or []
            off = body.get("offset")
            if isinstance(off, int) and off > rec["n_tokens"]:
                rec["gaps"] += 1
            fresh, n_seen = merge_stream_packet(rec["n_tokens"], off, toks)
            rec["dups"] += len(toks) - len(fresh)
            if fresh:
                if rec["first"] is None:
                    rec["first"] = now
                rec["last"] = now
                rec["n_tokens"] = n_seen
                rec["tokens"].extend(fresh)
                rec["packets"].append((now, len(fresh)))
        elif kind == "job_result" and rec["done"] is None:
            rec["done"] = now
            rec["status"] = body.get("status")
            self.spawn(self.finish(rec))

    def spawn(self, coro) -> None:
        t = asyncio.ensure_future(coro)
        self.tasks.add(t)
        t.add_done_callback(self.tasks.discard)

    async def finish(self, rec: dict) -> None:
        """The terminal result is authoritative (as in ``Client.generate``):
        read it back and hold the stream to it, then start what follows."""
        try:
            # the result packet reaches the tap before the scheduler has
            # written the terminal state: wait for that state, as
            # ``Client.wait_job`` does, while the drain lasts
            final = await self.client.wait_job(
                rec["job_id"], timeout_s=max(1.0, self.t_drain - time.monotonic()), poll_s=0.05)
            rec["state"] = final.get("state")
            result = [int(t) for t in (final.get("result") or {}).get("tokens") or []]
            rec["result_len"] = len(result)
            rec["stream_equals_result"] = result == rec["tokens"]
        except Exception as e:  # noqa: BLE001 - recorded per request, the run goes on
            rec["error"] = f"result: {type(e).__name__}: {e}"[:200]
        rec["event"].set()
        for nxt in self.followers.get(rec["i"], ()):
            due = rec["done"] + float(nxt.get("think_s", 0.0))
            if due < self.t_close:
                tokens = rec["prompt"] + rec["tokens"] + nxt["tokens"]
                self.spawn(self.send_at(nxt, due, tokens))

    # -- sending ----------------------------------------------------------
    async def send_at(self, req: dict, due: float, tokens: list[int]) -> None:
        delay = due - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        await self.send(req, due, tokens)

    async def send(self, req: dict, due: float, tokens: list[int]) -> dict:
        job_id = f"{self.tag}-{req['i']:05d}"
        rec = {"i": req["i"], "job_id": job_id, "due": due, "sent": time.monotonic(),
               "first": None, "last": None, "done": None, "n_tokens": 0, "tokens": [],
               "packets": [], "dups": 0, "gaps": 0, "status": None, "state": None,
               "error": None, "prompt": tokens, "prompt_len": len(tokens), "want": req["max_new_tokens"],
               "event": asyncio.Event()}
        rec["ramp"] = rec["sent"] < self.t0
        self.recs[job_id] = rec
        try:
            await self.client.submit_job(TOPIC, {
                "op": "llm.generate", "tokens": tokens, "session_id": req["session_id"],
                "max_new_tokens": req["max_new_tokens"], "stream": True,
            }, job_id=job_id)
        except Exception as e:  # noqa: BLE001 - a refused request is a failed request
            rec["error"] = f"submit: {type(e).__name__}: {e}"[:200]
            rec["done"] = time.monotonic()
            rec["event"].set()
        return rec

    async def open_loop(self) -> None:
        firsts = sorted((r for r in self.requests if "after" not in r),
                        key=lambda r: r["due_s"])
        for req in firsts:
            due = self.t0 + req["due_s"]
            if due >= self.t_close:
                break
            delay = due - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            # the submit's HTTP round trip must not hold the schedule back
            self.spawn(self.send(req, due, req["tokens"]))

    async def closed_loop(self) -> None:
        queue = iter([r for r in self.requests if "after" not in r])

        async def client_task() -> None:
            while time.monotonic() < self.t_close:
                req = next(queue, None)
                if req is None:
                    self.queue_exhausted = True
                    return
                now = time.monotonic()
                rec = await self.send(req, now, req["tokens"])
                await rec["event"].wait()

        delay = self.t_first - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        self.clients = [asyncio.ensure_future(client_task())
                        for _ in range(int(self.cmd["clients"]))]
        # a client waiting on a reply when the window closes is left to the drain
        await asyncio.wait(self.clients, timeout=max(0.0, self.t_close - time.monotonic()))

    async def watch_lag(self, out: dict) -> None:
        """This process's own lateness inside the window: a 20 ms ticker and
        the most it overslept.  A stall of the machine shows here as it does
        in the served path, so it is not read as the system's."""
        delay = self.t0 - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        last = time.monotonic()
        while last < self.t_close:
            await asyncio.sleep(0.02)
            now = time.monotonic()
            if now - last - 0.02 > out["loop_lag_max_s"]:
                out.update(loop_lag_max_s=now - last - 0.02, loop_lag_at_s=last - self.t0)
            last = now

    async def go(self) -> dict:
        lag = {"loop_lag_max_s": 0.0, "loop_lag_at_s": None}
        ticker = asyncio.ensure_future(self.watch_lag(lag))
        if self.loop_kind == "open":
            await self.open_loop()
        else:
            await self.closed_loop()
        delay = self.t_close - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        # bounded drain, outside the window: what was due gets to finish
        while time.monotonic() < self.t_drain:
            if all(r["event"].is_set() for r in self.recs.values()) and not self.tasks:
                break
            await asyncio.sleep(0.02)
        for t in self.clients + list(self.tasks) + [ticker]:
            t.cancel()
        out = []
        for rec in sorted(self.recs.values(), key=lambda r: r["i"]):
            rec = {k: v for k, v in rec.items() if k != "event"}
            rec["finished"] = rec["state"] is not None or rec["error"] is not None
            out.append(rec)
        return {"records": out, "queue_exhausted": self.queue_exhausted, **lag,
                "drained_s": max(0.0, time.monotonic() - self.t_close)}


async def tap_reader(ws, holder: dict) -> None:
    import aiohttp

    async for msg in ws:
        if msg.type not in (aiohttp.WSMsgType.TEXT, aiohttp.WSMsgType.BINARY):
            break
        run = holder.get("run")
        if run is None:
            continue
        pkt = json.loads(msg.data).get("packet")
        if pkt:
            run.on_packet(pkt)
    holder["tap_closed"] = True


async def serve(base_url: str, api_key: str) -> None:
    import aiohttp

    from cordum_tpu.sdk.client import Client

    loop = asyncio.get_running_loop()
    reader = asyncio.StreamReader(limit=2 ** 30)
    await loop.connect_read_pipe(lambda: asyncio.StreamReaderProtocol(reader), sys.stdin)
    holder: dict = {}
    n_runs = 0
    async with Client(base_url, api_key=api_key, timeout_s=120.0) as client, \
            aiohttp.ClientSession() as http:
        ws = await http.ws_connect(base_url + "/api/v1/stream",
                                   headers={"X-Api-Key": api_key}, max_msg_size=0)
        tap = asyncio.ensure_future(tap_reader(ws, holder))
        print(json.dumps({"ready": True, "pid": os.getpid()}), flush=True)
        while True:
            line = await reader.readline()
            if not line:
                break
            cmd = json.loads(line)
            if cmd["cmd"] == "quit":
                break
            n_runs += 1
            holder["run"] = run = Run(client, cmd, tag=f"{cmd.get('tag', 'b')}{n_runs}")
            answer = await run.go()
            holder["run"] = None
            answer["tap_closed"] = bool(holder.get("tap_closed"))
            answer["jax_imported"] = "jax" in sys.modules
            print(json.dumps(answer), flush=True)
        tap.cancel()
        await ws.close()


def main() -> int:
    asyncio.run(serve(sys.argv[1], sys.argv[2]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
