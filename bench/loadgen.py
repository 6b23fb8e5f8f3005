"""Open-loop HTTP load generator: a child process on the standard library.

    python3 loadgen.py < plan.json > results.json

The plan (stdin, JSON) holds ``host``, ``port``, ``t0`` (an absolute
``time.monotonic()`` reading, shared with the parent because both read the
system's monotonic clock), ``workers``, ``give_up`` (seconds after ``t0``
after which unanswered requests are abandoned) and ``requests``: a list of
``{"t": offset_s, "path": "/query" | "/update", "body": {...}}``.

One scheduler thread releases each request at ``t0 + t`` to a pool of
worker threads, each with its own keep-alive connection; a request waits
for a free worker if all are busy, and that wait shows as lateness.  For
every request the result records its scheduled, sent and received times
(monotonic seconds), the HTTP status (``0`` when no answer came) and the
decoded body.  Nothing here imports JAX or the program.
"""
from __future__ import annotations

import http.client
import json
import queue
import sys
import threading
import time


def run(plan: dict) -> dict:
    host, port, t0 = plan["host"], int(plan["port"]), float(plan["t0"])
    reqs = plan["requests"]
    give_up = t0 + float(plan["give_up"])
    results = [
        {"t_sched": t0 + r["t"], "t_send": None, "t_recv": None,
         "status": 0, "body": None}
        for r in reqs
    ]
    work: queue.Queue = queue.Queue()

    def worker() -> None:
        conn = None
        while True:
            i = work.get()
            if i is None:
                break
            r, out = reqs[i], results[i]
            payload = json.dumps(r["body"]).encode()
            for attempt in (0, 1):  # one reconnect on a stale socket
                if conn is None:
                    conn = http.client.HTTPConnection(
                        host, port, timeout=max(1.0, give_up - time.monotonic())
                    )
                try:
                    out["t_send"] = time.monotonic()
                    conn.request("POST", r["path"], body=payload,
                                 headers={"Content-Type": "application/json"})
                    resp = conn.getresponse()
                    data = resp.read()
                    out["t_recv"] = time.monotonic()
                    out["status"] = resp.status
                    out["body"] = json.loads(data) if data else {}
                    break
                except (http.client.HTTPException, OSError, ValueError):
                    conn.close()
                    conn = None
                    if attempt or time.monotonic() > give_up:
                        break

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(int(plan["workers"]))]
    for th in threads:
        th.start()
    order = sorted(range(len(reqs)), key=lambda i: reqs[i]["t"])
    for i in order:
        delay = results[i]["t_sched"] - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        work.put(i)
    for _ in threads:
        work.put(None)
    for th in threads:
        th.join(timeout=max(0.0, give_up - time.monotonic()))
    # answers still outstanding at give_up stay status 0 (never answered);
    # copy so a late worker cannot change what is reported
    return {"results": [dict(r) for r in results]}


def main() -> None:
    plan = json.load(sys.stdin)
    json.dump(run(plan), sys.stdout)
    sys.stdout.flush()


if __name__ == "__main__":
    main()
