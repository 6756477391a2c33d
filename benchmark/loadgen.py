"""The open-loop load generator: a child process that never imports JAX.

    python benchmark/loadgen.py --plan <plan.json> --out <results.json>

The plan holds the server's address, the window's length, how long to wait
for stragglers after it, and every request with its due time.  The child
reads it, prints ``ready``, and waits on standard input for ``go <t0>``,
where ``t0`` is a ``time.monotonic()`` instant (one clock for every process
of a Linux machine).  Request ``i`` is sent at ``t0 + due_s[i]`` whether or
not earlier ones have finished; every time it records is seconds from
``t0``.  One thread, ``asyncio`` streams: no thread per request to fight
the server for the host's cores.

What is still unfinished ``drain_s`` after the window is cancelled at the
server (``POST /v1/cancel``) and recorded as ``unfinished``.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time


async def _post(host, port, path, body: dict):
    """One blocking-style POST; returns (status, parsed body or None)."""
    reader, writer = await asyncio.open_connection(host, port)
    try:
        data = json.dumps(body).encode()
        writer.write((f"POST {path} HTTP/1.1\r\nHost: {host}\r\n"
                      f"Content-Type: application/json\r\n"
                      f"Content-Length: {len(data)}\r\n"
                      f"Connection: close\r\n\r\n").encode() + data)
        await writer.drain()
        status = int((await reader.readline()).split()[1])
        while (await reader.readline()) not in (b"\r\n", b"\n", b""):
            pass
        raw = await reader.read()
        try:
            return status, json.loads(raw)
        except ValueError:
            return status, None
    finally:
        writer.close()


async def _one(i: int, req: dict, plan: dict, t0: float, rec: dict):
    """Send request ``i`` when it is due and follow its stream."""
    delay = t0 + req["due_s"] - time.monotonic()
    if delay > 0:
        await asyncio.sleep(delay)
    rec.update(i=i, due_s=req["due_s"], prompt_len=len(req["prompt"]),
               asked=req["max_new_tokens"], status="error:unsent",
               events=[])
    body = {"prompt_tokens": req["prompt"],
            "max_new_tokens": req["max_new_tokens"],
            "stream": bool(req.get("stream", True))}
    data = json.dumps(body).encode()
    writer = None
    try:
        reader, writer = await asyncio.open_connection(
            plan["host"], plan["port"])
        rec["sent_s"] = time.monotonic() - t0
        writer.write((f"POST /v1/completions HTTP/1.1\r\n"
                      f"Host: {plan['host']}\r\n"
                      f"Content-Type: application/json\r\n"
                      f"Content-Length: {len(data)}\r\n"
                      f"Connection: close\r\n\r\n").encode() + data)
        await writer.drain()
        status = int((await reader.readline()).split()[1])
        while (await reader.readline()) not in (b"\r\n", b"\n", b""):
            pass
        if status != 200:
            rec["status"] = f"http_{status}"
            rec["done_s"] = time.monotonic() - t0
            return
        if not body["stream"]:
            final = json.loads(await reader.read())
            now = time.monotonic() - t0
            rec.update(first_s=now, done_s=now, id=final.get("id"),
                       new_tokens=final.get("new_tokens", []))
        else:
            seen = 0
            while True:
                line = await reader.readline()
                if not line:
                    rec["status"] = "truncated"
                    return
                if not line.startswith(b"data: "):
                    continue
                now = time.monotonic() - t0
                event = json.loads(line[6:])
                rec.setdefault("id", event.get("id"))
                if event.get("done"):
                    if "new_tokens" not in event:
                        rec["status"] = "error:" + ",".join(
                            k for k in event if k not in ("id", "done"))
                        rec["done_s"] = now
                        return
                    rec["new_tokens"] = event["new_tokens"]
                    if len(event["new_tokens"]) > seen:
                        rec["events"].append((now, len(event["new_tokens"])))
                        rec.setdefault("first_s", now)
                    rec["done_s"] = now
                    break
                if "error" in event:
                    rec["status"] = "error:" + str(event["error"])
                    rec["done_s"] = now
                    return
                if event.get("new_tokens"):
                    seen += len(event["new_tokens"])
                    rec["events"].append((now, seen))
                    rec.setdefault("first_s", now)
        got = len(rec.get("new_tokens", []))
        rec["status"] = "ok" if got == req["max_new_tokens"] \
            else f"truncated:{got}"
    except asyncio.CancelledError:
        rec["status"] = "unfinished"
        raise
    except (OSError, ValueError, IndexError) as e:
        rec["status"] = f"error:{type(e).__name__}:{e}"
        rec["done_s"] = time.monotonic() - t0
    finally:
        if writer is not None:
            writer.close()


async def _run(plan: dict, t0: float) -> list:
    recs = [{} for _ in plan["requests"]]
    tasks = [asyncio.ensure_future(_one(i, r, plan, t0, recs[i]))
             for i, r in enumerate(plan["requests"])]
    deadline = t0 + plan["seconds"] + plan["drain_s"]
    _, pending = await asyncio.wait(
        tasks, timeout=max(0.0, deadline - time.monotonic()))
    for t in pending:
        t.cancel()
    if pending:
        await asyncio.wait(pending)
    for rec in recs:
        if rec.get("status") == "unfinished" and rec.get("id") is not None:
            try:
                await _post(plan["host"], plan["port"], "/v1/cancel",
                            {"id": rec["id"]})
            except OSError:
                pass
    return recs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--plan", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    with open(args.plan) as f:
        plan = json.load(f)
    print("ready", flush=True)
    word = sys.stdin.readline().split()
    if len(word) != 2 or word[0] != "go":
        return 2
    recs = asyncio.run(_run(plan, float(word[1])))
    with open(args.out, "w") as f:
        json.dump(recs, f)
    print("done", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
