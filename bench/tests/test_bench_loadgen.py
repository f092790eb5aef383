"""The open-loop client times every request from its due time."""

import asyncio
import json
import os
import subprocess
import sys
import threading
import time

import tiny
from bench import stats

LOADGEN = os.path.join(tiny.BENCH, "loadgen.py")


class SlowServer:
    """A stand-in front door that streams ``n`` tokens per request; the
    first request it sees is held for ``stall_s`` before its first token,
    and its handler never blocks the others."""

    def __init__(self, stall_s: float, gap_s: float = 0.005):
        self.stall_s, self.gap_s = stall_s, gap_s
        self.seen = []
        self.loop = asyncio.new_event_loop()
        ready = threading.Event()

        def serve():
            asyncio.set_event_loop(self.loop)
            srv = self.loop.run_until_complete(
                asyncio.start_server(self._handle, "127.0.0.1", 0))
            self.port = srv.sockets[0].getsockname()[1]
            self.srv = srv
            ready.set()
            self.loop.run_forever()

        self.thread = threading.Thread(target=serve, daemon=True)
        self.thread.start()
        ready.wait(10)

    async def _handle(self, reader, writer):
        head = await reader.readuntil(b"\r\n\r\n")
        n = int([h for h in head.split(b"\r\n")
                 if h.lower().startswith(b"content-length")][0].split(b":")[1])
        body = json.loads(await reader.readexactly(n))
        first = not self.seen
        self.seen.append(time.monotonic())
        writer.write(b"HTTP/1.1 200 OK\r\nContent-Type: text/event-stream"
                     b"\r\n\r\n")
        if first:
            await asyncio.sleep(self.stall_s)
        for i in range(body["max_new_tokens"]):
            await asyncio.sleep(self.gap_s)
            writer.write(b"data: " + json.dumps(
                {"token": 7, "index": i, "request_id": 1}).encode() + b"\n\n")
            await writer.drain()
        writer.write(b"data: " + json.dumps(
            {"done": True, "finish_reason": "length"}).encode() + b"\n\n")
        await writer.drain()
        writer.close()

    def close(self):
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(10)


def run_client(port, reqs, window_s, hard_stop_s, tmp_path):
    job = tmp_path / "job.json"
    job.write_text(json.dumps({"host": "127.0.0.1", "port": port,
                               "window_s": window_s,
                               "hard_stop_s": hard_stop_s,
                               "requests": reqs}))
    p = subprocess.Popen([sys.executable, LOADGEN, str(job)],
                         stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                         text=True)
    assert p.stdout.readline().strip() == "ready"
    t0 = time.monotonic() + 0.05
    p.stdin.write(f"{t0!r}\n")
    p.stdin.flush()
    out, _ = p.communicate(timeout=60)
    assert p.returncode == 0
    return json.loads(out)["requests"], t0


def test_open_loop_sends_on_schedule_and_times_from_due(tmp_path):
    srv = SlowServer(stall_s=0.6)
    try:
        reqs = [{"id": i, "due": 0.1 * i, "prompt": [3, 4],
                 "max_new_tokens": 3} for i in range(8)]
        recs, t0 = run_client(srv.port, reqs, 2.0, 20.0, tmp_path)
    finally:
        srv.close()
    # requests behind the stalled one still went out when due
    late = [r["sent"] - r["due"] for r in recs]
    assert max(late) < 0.05
    assert all(r["ok"] and len(r["tokens_t"]) == 3 for r in recs)
    # the stalled request's TTFT counts the stall, from its due time
    ttft = sorted(r["tokens_t"][0] - r["due"] for r in recs)
    assert ttft[-1] >= 0.6 and ttft[0] < 0.1
    assert stats.lateness_p99_ms(recs) < 50


def test_client_stops_after_the_window_and_hangs_up_on_the_rest(tmp_path):
    srv = SlowServer(stall_s=0.0, gap_s=0.05)
    try:
        reqs = [{"id": 0, "due": 0.0, "prompt": [3], "max_new_tokens": 4},
                {"id": 1, "due": 0.5, "prompt": [3], "max_new_tokens": 4},
                {"id": 2, "due": 30.0, "prompt": [3], "max_new_tokens": 4}]
        t = time.monotonic()
        recs, _ = run_client(srv.port, reqs, 0.4, 20.0, tmp_path)
    finally:
        srv.close()
    assert time.monotonic() - t < 10
    assert recs[0]["ok"]
    assert recs[2]["sent"] is None and not recs[2]["ok"]
