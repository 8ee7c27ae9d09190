"""Open-loop load over HTTP + SSE, timed from when each request was due.

One scheduler thread per request would be simplest, and is what
``benchmarks/serve_http.py`` does, but that script starts its clock at the
send: a stalled generator then hides the wait it imposes. Here every time is
taken against the request's due time, and how late each send ran is reported.
Copied idea, own clock; the original stays in ``benchmarks/`` (PERF.md, Open
questions).
"""

from __future__ import annotations

import http.client
import json
import threading
import time
from typing import List, Optional


def _blank_record(req: dict, t_zero: float) -> dict:
    return {
        "due": t_zero + req["due_s"], "in_window": req["in_window"],
        "n_prompt": len(req["prompt"]), "n_asked": req["max_new_tokens"],
        "tokens": [], "t_tokens": [], "error": None, "rid": None, "sent": float("nan"),
    }


def _stream_one(host: str, port: int, req: dict, t_zero: float, timeout: float) -> dict:
    """Send one request at its due time and read its token events."""
    rec = _blank_record(req, t_zero)
    delay = rec["due"] - time.perf_counter()
    if delay > 0:
        time.sleep(delay)
    rec["sent"] = time.perf_counter()
    try:
        conn = http.client.HTTPConnection(host, port, timeout=timeout)
        try:
            conn.request(
                "POST", "/predict/stream",
                body=json.dumps({"features": req["prompt"],
                                 "max_new_tokens": req["max_new_tokens"]}),
                headers={"Content-Type": "application/json"},
            )
            resp = conn.getresponse()
            rec["rid"] = resp.getheader("X-Request-ID")
            if resp.status != 200:
                rec["error"] = f"HTTP {resp.status}: {resp.read()[:200]!r}"
                return rec
            buf = b""
            done = False
            while not done:
                chunk = resp.read1(65536)
                if not chunk:
                    break
                now = time.perf_counter()
                buf += chunk
                while b"\n\n" in buf:
                    event, buf = buf.split(b"\n\n", 1)
                    if not event.startswith(b"data: "):
                        continue
                    data = json.loads(event[6:])
                    if "tokens" in data:
                        rec["tokens"].extend(data["tokens"])
                        rec["t_tokens"].extend([now] * len(data["tokens"]))
                    elif data.get("done"):
                        done = True
            if not done:
                rec["error"] = "stream ended without its done event"
        finally:
            conn.close()
    except (OSError, http.client.HTTPException, ValueError) as exc:
        rec["error"] = repr(exc)
    return rec


def run_open_loop(host: str, port: int, requests: List[dict], *, timeout: float = 120.0,
                  hooks: Optional[List[tuple]] = None) -> dict:
    """Drive ``requests`` (see :func:`chipbench.traffic.draw_requests`) and
    return ``{"records": [...], "t_zero": <perf_counter at the window's start>}``.
    ``hooks`` are ``(seconds from the window's start, callable)`` pairs.

    Every request and every hook gets a thread of its own, made before the
    first is due and asleep until its time: sleeping threads cost nothing,
    and no send then waits on another thread's start (a scheduler that
    started them one by one ran a second late when the interpreter stalled,
    and took the next sends with it). ``records[i]`` belongs to ``requests[i]``."""
    first_due = min(r["due_s"] for r in requests)
    n_threads = len(requests) + len(hooks or [])
    t_zero = time.perf_counter() + 0.25 + 0.002 * n_threads - first_due  # the window's start on this clock
    records: List[dict] = [None] * len(requests)

    def worker(i: int) -> None:
        records[i] = _stream_one(host, port, requests[i], t_zero, timeout)

    def hook(at: float, fn) -> None:
        wait = t_zero + at - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        fn()

    threads = [threading.Thread(target=worker, args=(i,), daemon=True) for i in range(len(requests))]
    hook_threads = [threading.Thread(target=hook, args=(float(at), fn), daemon=True) for at, fn in hooks or []]
    for t in threads + hook_threads:
        t.start()
    deadline = t_zero + max(r["due_s"] for r in requests) + timeout
    for t in threads:
        t.join(max(0.0, deadline - time.perf_counter()))
    for t in hook_threads:
        t.join()
    for i, rec in enumerate(records):
        if rec is None:  # its thread outlived the drain deadline
            records[i] = dict(_blank_record(requests[i], t_zero), error="not finished by the drain deadline")
    return {"records": records, "t_zero": t_zero}
