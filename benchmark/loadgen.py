"""The load generator: a process of its own that never imports JAX, so its
timers do not share the server's interpreter lock.

    python -m benchmark.loadgen <schedule.json> <results.json>

One thread, asyncio, a minimal HTTP/1.1 client over raw streams. Every
request is ``POST /v1/completions`` with ``stream: true``; a token frame
carries one token id (the server runs without a tokenizer, so prompts are id
lists and frames carry ``tokens``). All times are ``time.monotonic()``, the
machine-wide clock the harness uses too: the schedule's ``t0`` is an absolute
instant on it. An open-loop request is timed from when it was due, and how
late it left is recorded beside it.
"""

from __future__ import annotations

import asyncio
import json
import sys
import time


async def one_request(host: str, port: int, model: str, req: dict,
                      due_at: float, deadline: float) -> dict:
    """Send one streamed completion; -> its timing record."""
    rec = {"id": req["id"], "due": due_at, "sent": None, "times": [],
           "tokens": [], "asked": req["max_tokens"], "error": None,
           "n_prompt": len(req["prompt"]), "done": None}
    body = json.dumps({
        "model": model, "prompt": req["prompt"],
        "max_tokens": req["max_tokens"], "temperature": 0, "stream": True,
    }).encode()
    head = (
        f"POST /v1/completions HTTP/1.1\r\nHost: {host}:{port}\r\n"
        "Content-Type: application/json\r\nConnection: close\r\n"
        f"Content-Length: {len(body)}\r\n\r\n"
    ).encode()
    writer = None
    try:
        reader, writer = await asyncio.open_connection(host, port)
        rec["sent"] = time.monotonic()
        writer.write(head + body)
        await writer.drain()
        status = await asyncio.wait_for(reader.readline(), deadline - time.monotonic())
        if b" 200 " not in status:
            rest = await asyncio.wait_for(reader.read(600), 5.0)
            rec["error"] = (status + rest).decode("utf-8", "replace")[:300]
            return rec
        while True:
            left = deadline - time.monotonic()
            if left <= 0:
                rec["error"] = "deadline"
                return rec
            line = await asyncio.wait_for(reader.readline(), left)
            if not line:
                rec["error"] = rec["error"] or "stream ended without [DONE]"
                return rec
            if not line.startswith(b"data:"):
                continue  # headers, chunk sizes, id lines, blanks
            now = time.monotonic()
            payload = line[5:].strip()
            if payload == b"[DONE]":
                rec["done"] = now
                return rec
            frame = json.loads(payload)
            if "error" in frame:
                rec["error"] = str(frame["error"])[:300]
                return rec
            for choice in frame.get("choices", ()):
                for token in choice.get("tokens") or ():
                    rec["tokens"].append(token)
                    rec["times"].append(now)
    except (OSError, asyncio.TimeoutError, ValueError) as exc:
        rec["error"] = f"{type(exc).__name__}: {exc}"[:300]
        return rec
    finally:
        if writer is not None:
            writer.close()


async def open_loop(sched: dict) -> list[dict]:
    t0, reqs = sched["t0"], sched["requests"]
    deadline = t0 + sched["ramp_s"] + sched["seconds"] + sched["drain_s"]
    tasks = []
    for req in reqs:  # sorted by due time
        due_at = t0 + req["due"]
        delay = due_at - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        tasks.append(asyncio.ensure_future(one_request(
            sched["host"], sched["port"], sched["model"], req, due_at, deadline
        )))
    return list(await asyncio.gather(*tasks))


async def closed_loop(sched: dict) -> list[dict]:
    """``clients`` callers, each sending its next request when the last one
    ends, from the ramp's start to the window's end; what is in flight then
    is given ``drain_s`` to finish."""
    t0, reqs = sched["t0"], sched["requests"]
    stop = t0 + sched["ramp_s"] + sched["seconds"]
    deadline = stop + sched["drain_s"]
    state = {"next": 0}
    records: list[dict] = []

    async def client() -> None:
        while time.monotonic() < stop:
            i = state["next"]
            state["next"] = i + 1
            req = dict(reqs[i % len(reqs)], id=i)  # wraps when exhausted
            records.append(await one_request(
                sched["host"], sched["port"], sched["model"], req,
                time.monotonic(), deadline,
            ))

    delay = t0 - time.monotonic()
    if delay > 0:
        await asyncio.sleep(delay)
    await asyncio.gather(*(client() for _ in range(sched["clients"])))
    return records


def main(argv: list[str]) -> int:
    with open(argv[1], encoding="utf-8") as fh:
        sched = json.load(fh)
    run = closed_loop if sched["loop"] == "closed" else open_loop
    records = asyncio.run(run(sched))
    with open(argv[2] + ".tmp", "w", encoding="utf-8") as fh:
        json.dump({"records": records}, fh)
    import os

    os.replace(argv[2] + ".tmp", argv[2])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
