"""Clients of the served path: ``POST /v1/generate`` with SSE on localhost.

The SSE client and the Poisson schedule follow ``benchmarks/slo_harness.py``,
repaired: every request is timed from the moment it was due, not from when
its coroutine woke up, so a late generator shows as late first tokens
instead of a fast server; the lateness itself is kept per request.  Each
token event is stamped on arrival with the event loop's clock
(``time.monotonic``), so the window's metrics are taken on the client side.
"""
from __future__ import annotations

import asyncio
import itertools
import json
from typing import Dict, List, Optional

from bench.traffic import RequestSpec


def new_record(spec_idx: int, prompt: List[int], max_new: int,
               due: float) -> Dict:
    return {"idx": spec_idx, "prompt": prompt, "max_new": max_new,
            "due": due, "sent": None, "events": [], "tokens": [],
            "done": None, "status": None, "error": None}


async def sse_request(host: str, port: int, rec: Dict) -> None:
    """Send one request and stream it to its end, filling ``rec``."""
    loop = asyncio.get_running_loop()
    rec["sent"] = loop.time()
    writer = None
    try:
        reader, writer = await asyncio.open_connection(host, port)
        body = json.dumps({"prompt": rec["prompt"], "max_new": rec["max_new"],
                           "stream": True}).encode()
        writer.write(b"POST /v1/generate HTTP/1.1\r\n"
                     + f"Host: {host}\r\n".encode()
                     + f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
        await writer.drain()
        status_line = (await reader.readline()).decode()
        rec["status"] = int(status_line.split(" ", 2)[1])
        if rec["status"] != 200:
            rec["error"] = (await reader.read()).decode()[-300:]
            return
        event = ""
        while True:
            line = await reader.readline()
            if not line:
                break
            line = line.decode().strip()
            if line.startswith("event: "):
                event = line[7:]
            elif line.startswith("data: "):
                now = loop.time()
                data = json.loads(line[6:])
                if event == "token":
                    rec["events"].append((now, len(data["tokens"])))
                    rec["tokens"].extend(data["tokens"])
                elif event == "done":
                    rec["done"] = {"t": now, "tokens": data["tokens"],
                                   "generated": data["generated"]}
                    if data["tokens"] != rec["tokens"]:
                        rec["error"] = "SSE token events disagree with done"
                    return
        rec["error"] = "stream ended without a done event"
    except (ConnectionError, OSError, ValueError, IndexError) as e:
        rec["error"] = repr(e)
    finally:
        if writer is not None:
            writer.close()


async def _run_until(tasks: List[asyncio.Task], t_end: float) -> None:
    loop = asyncio.get_running_loop()
    await asyncio.sleep(max(0.0, t_end - loop.time()))
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)


async def drive_open(host: str, port: int, specs: List[RequestSpec],
                     t_start: float, t_end: float,
                     records: List[Dict]) -> None:
    """Open loop: request ``i`` is due at ``t_start + specs[i].offset_s``
    and is sent then, whatever the server is doing; nothing due at or
    after ``t_end`` is sent."""
    loop = asyncio.get_running_loop()

    async def one(i: int, s: RequestSpec):
        due = t_start + s.offset_s
        await asyncio.sleep(max(0.0, due - loop.time()))
        rec = new_record(i, s.prompt, s.max_new, due)
        records.append(rec)
        await sse_request(host, port, rec)

    tasks = [asyncio.ensure_future(one(i, s)) for i, s in enumerate(specs)
             if t_start + s.offset_s < t_end]
    await _run_until(tasks, t_end)


async def drive_closed(host: str, port: int, specs: List[RequestSpec],
                       first_budgets: List[int], t_end: float,
                       records: List[Dict]) -> None:
    """Closed loop: ``len(first_budgets)`` clients, each sending the next
    request of the shared pool as soon as its last one is done; a
    request is due when its client sends it."""
    loop = asyncio.get_running_loop()
    pool = itertools.cycle(range(len(specs)))

    async def client(c: int):
        budget: Optional[int] = first_budgets[c]
        while loop.time() < t_end:
            i = next(pool)
            s = specs[i]
            rec = new_record(i, s.prompt, budget or s.max_new, loop.time())
            budget = None
            records.append(rec)
            await sse_request(host, port, rec)
            if rec["error"] is not None:
                return

    tasks = [asyncio.ensure_future(client(c))
             for c in range(len(first_budgets))]
    await _run_until(tasks, t_end)
