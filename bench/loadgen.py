"""Open-loop client: sends a schedule of streamed requests to the front
door and times every token from the client's side.  Never imports JAX.

    python3 bench/loadgen.py JOB.json   # prints "ready", reads t0, runs

The job file holds ``host``, ``port``, ``window_s``, ``hard_stop_s`` and
``requests`` (``id``, ``due``, ``prompt``, ``max_new_tokens``).  After
printing ``ready`` the client reads one line from stdin: the
``time.monotonic()`` instant the window opens.  Each request is sent at
its due time whatever happened to earlier ones.  Once every request due
in the window has finished, the client stops sending and hangs up on the
rest.  It prints one JSON object: per request its ``due``, ``sent``,
``tokens_t`` (arrival of each token), ``tokens``, ``finish_reason``,
``request_id`` and ``ok``, all times in seconds after the window opened.
"""

from __future__ import annotations

import asyncio
import json
import sys
import time


async def _one(req: dict, t0: float, host: str, port: int, rec: dict):
    await asyncio.sleep(max(0.0, t0 + req["due"] - time.monotonic()))
    body = json.dumps({"prompt": req["prompt"],
                       "max_new_tokens": req["max_new_tokens"],
                       "stream": True}).encode()
    head = (f"POST /v1/generate HTTP/1.1\r\nHost: {host}:{port}\r\n"
            f"Content-Length: {len(body)}\r\nConnection: close\r\n\r\n")
    reader, writer = await asyncio.open_connection(host, port)
    try:
        writer.write(head.encode() + body)
        await writer.drain()
        rec["sent"] = time.monotonic() - t0
        status = await reader.readline()
        if b" 200 " not in status:
            rec["error"] = status.decode("latin-1").strip()
            return
        await reader.readuntil(b"\r\n\r\n")
        buf = b""
        while True:
            chunk = await reader.read(65536)
            now = time.monotonic() - t0
            if not chunk:
                rec["error"] = "server closed the stream"
                return
            buf += chunk
            while b"\n\n" in buf:
                raw, buf = buf.split(b"\n\n", 1)
                if not raw.startswith(b"data: "):
                    continue
                ev = json.loads(raw[6:])
                rec["request_id"] = ev.get("request_id", rec["request_id"])
                if ev.get("done"):
                    rec["finish_reason"] = ev["finish_reason"]
                    rec["ok"] = (ev["finish_reason"] == "length" and len(
                        rec["tokens"]) == req["max_new_tokens"])
                    return
                rec["tokens"].append(int(ev["token"]))
                rec["tokens_t"].append(now)
    finally:
        writer.close()


async def run(job: dict, t0: float) -> list:
    host, port = job["host"], int(job["port"])
    window_s = float(job["window_s"])
    recs = [{"id": r["id"], "due": r["due"], "sent": None, "tokens_t": [],
             "tokens": [], "finish_reason": None, "request_id": None,
             "ok": False, "error": None} for r in job["requests"]]
    tasks = [asyncio.ensure_future(_one(r, t0, host, port, rec))
             for r, rec in zip(job["requests"], recs)]
    counted = [t for t, r in zip(tasks, job["requests"])
               if r["due"] < window_s]
    deadline = t0 + float(job["hard_stop_s"])
    await asyncio.wait(counted, timeout=max(0.0, deadline - time.monotonic()))
    for t, rec in zip(tasks, recs):
        if not t.done():
            t.cancel()
            if rec["error"] is None:
                rec["error"] = "unfinished when the client stopped"
    for t, rec in zip(tasks, recs):
        try:
            await t
        except asyncio.CancelledError:
            pass
        except (OSError, ValueError, asyncio.IncompleteReadError) as e:
            rec["error"] = f"{type(e).__name__}: {e}"
    return recs


def main(argv) -> int:
    with open(argv[1], encoding="utf-8") as f:
        job = json.load(f)
    print("ready", flush=True)
    t0 = float(sys.stdin.readline())
    recs = asyncio.run(run(job, t0))
    json.dump({"requests": recs}, sys.stdout)
    sys.stdout.write("\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
