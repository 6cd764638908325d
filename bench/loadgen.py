"""Load generator: the child process that plays a traffic plan against
the server over HTTP/SSE (``POST /v1/generate``) on loopback.

It never imports JAX and runs one asyncio loop in one thread, so it
shares neither the chip nor the server's interpreter lock.  It rebuilds
the plan from the same arguments as the harness (``traffic.build`` is
deterministic) and prints one JSON object on stdout when it is done:

  {"t0": window open, "t1": window close (time.monotonic seconds, a
   clock every process of the machine shares),
   "requests": [{"stream", "idx", "sent", "first", "end",
                 "times": [token arrival], "tokens": [token ids],
                 "status": "done" | "cut" | "failed", "error"}, ...]}

Window.  The loop is closed: each stream sends its next request when
its last one ends.  The window opens once every stream has received the
first token of its first request (the ramp before it is set-up), and
lasts ``--seconds``; no request is sent after it closes, and requests
still streaming then are cut (status "cut": in flight, not failed).

A request that errors, or is refused (429 or 503), has status "failed".

  python bench/loadgen.py --port P --traffic bench/traffic/x.json \\
      --seed N --seconds S --vocab V --max-seq M
"""
from __future__ import annotations

import argparse
import asyncio
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import traffic  # noqa: E402

RAMP_TIMEOUT_S = 300.0  # every stream's first token by then


class Failed(Exception):
    pass


async def _read_head(reader) -> tuple[int, dict]:
    line = await reader.readline()
    parts = line.decode("latin-1").split(None, 2)
    if len(parts) < 2 or not parts[0].startswith("HTTP/"):
        raise Failed(f"malformed status line {line!r}")
    headers = {}
    while True:
        line = await reader.readline()
        if line in (b"\r\n", b"\n", b""):
            break
        name, _, value = line.decode("latin-1").partition(":")
        headers[name.strip().lower()] = value.strip()
    return int(parts[1]), headers


async def _events(reader):
    """(event, data) pairs of an SSE stream."""
    event, data = None, []
    while True:
        line = await reader.readline()
        if not line:
            return
        text = line.rstrip(b"\r\n").decode("utf-8")
        if not text:
            if data:
                yield event or "message", json.loads("\n".join(data))
            event, data = None, []
        elif text.startswith(":"):
            continue
        else:
            name, _, value = text.partition(":")
            value = value[1:] if value.startswith(" ") else value
            if name == "event":
                event = value
            elif name == "data":
                data.append(value)


def _body(plan: dict, req: dict) -> dict:
    return {"prompt": traffic.prompt(plan, req),
            "max_new_tokens": req["max_new_tokens"]}


async def run_request(host, port, plan, req, rec: dict,
                      on_first=None) -> None:
    """Send one request and record its tokens as they stream in."""
    rec["sent"] = time.monotonic()
    writer = None
    try:
        reader, writer = await asyncio.open_connection(host, port)
        body = json.dumps(_body(plan, req)).encode()
        head = ["POST /v1/generate HTTP/1.1", f"host: {host}",
                "connection: close", "content-type: application/json",
                f"content-length: {len(body)}"]
        writer.write(("\r\n".join(head) + "\r\n\r\n").encode() + body)
        await writer.drain()
        status, headers = await _read_head(reader)
        if status != 200 or "text/event-stream" not in \
                headers.get("content-type", ""):
            raise Failed(f"HTTP {status}")
        async for event, data in _events(reader):
            if event == "done":
                rec["status"] = "done"
                break
            now = time.monotonic()
            if rec["first"] is None:
                rec["first"] = now
                if on_first is not None:
                    on_first()
            rec["times"].append(now)
            rec["tokens"].append(int(data["token"]))
        else:
            raise Failed("stream closed before its end")
    except asyncio.CancelledError:
        rec["status"] = "cut"
        raise
    except (Failed, OSError, ValueError, KeyError) as e:
        rec["status"] = "failed"
        rec["error"] = repr(e)
    finally:
        rec["end"] = time.monotonic()
        if writer is not None:
            writer.close()
            with contextlib.suppress(Exception):
                await writer.wait_closed()


def _record(stream, idx) -> dict:
    return {"stream": stream, "idx": idx, "sent": None,
            "first": None, "end": None, "times": [], "tokens": [],
            "status": "cut", "error": None}


async def closed_loop(host, port, plan, seconds) -> dict:
    streams = plan["streams"]
    records: list[dict] = []
    started = [False] * len(streams)
    opened = asyncio.Event()
    window = {}

    def first_token(k):
        def mark():
            if not started[k]:
                started[k] = True
                if all(started):
                    window["t0"] = time.monotonic()
                    opened.set()
        return mark

    async def client(k):
        for j, req in enumerate(streams[k]):
            if "t1" in window and time.monotonic() >= window["t1"]:
                return
            rec = _record(k, j)
            records.append(rec)
            await run_request(host, port, plan, req, rec, first_token(k))
            if rec["status"] == "failed":
                first_token(k)()
        raise RuntimeError(f"stream {k} ran out of requests: raise rounds")

    tasks = [asyncio.ensure_future(client(k)) for k in range(len(streams))]
    try:
        await asyncio.wait_for(opened.wait(), RAMP_TIMEOUT_S)
        window["t1"] = window["t0"] + seconds
        await asyncio.sleep(max(0.0, window["t1"] - time.monotonic()))
    finally:
        for t in tasks:
            t.cancel()
        done = await asyncio.gather(*tasks, return_exceptions=True)
    for r in done:
        if isinstance(r, BaseException) and \
                not isinstance(r, asyncio.CancelledError):
            raise r
    return {"t0": window["t0"], "t1": window["t1"], "requests": records}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--vocab", type=int, required=True)
    ap.add_argument("--max-seq", type=int, required=True)
    args = ap.parse_args(argv)
    mix = traffic.load(args.traffic)
    plan = traffic.build(mix, args.seed, args.vocab, args.max_seq)
    out = asyncio.run(closed_loop(args.host, args.port, plan, args.seconds))
    sys.stdout.write(json.dumps(out) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
