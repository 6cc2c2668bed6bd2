#!/usr/bin/env python3
"""Open-loop load generator: a child process that sends a schedule of
queries through ``repro.api`` over TCP and records when each was due,
sent and answered.

    python bench/loadgen.py <schedule.json> <results.json>

It connects its clients, prints ``ready``, and waits for a line
``go <t0>`` on standard input, ``t0`` a ``time.monotonic()`` instant
(the clock is shared by every process of the machine).  Request ``i``
is due at ``t0 + due[i]``; one thread releases requests at their due
times to a few client threads, each with its own connection, so a slow
answer delays only what waits behind it, and each request's latency runs
from its due time to its decoded answer.  A request unanswered
``drain_s`` after the last due time, or answered with an error, is
failed.  It never touches a device: ``JAX_PLATFORMS`` is ``cpu`` in its
environment and nothing it calls initialises a backend.
"""

from __future__ import annotations

import json
import os
import queue
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))


def main(schedule_path: str, results_path: str) -> int:
    from repro import api

    with open(schedule_path, encoding="utf-8") as f:
        sched = json.load(f)
    texts, due = sched["texts"], sched["due"]
    n = len(due)
    sent = [None] * n
    done = [None] * n
    error = [None] * n
    answers: list = [None] * n
    sessions = [api.connect(sched["address"], timeout=sched["drain_s"])
                for _ in range(sched["clients"])]
    work: queue.Queue = queue.Queue()

    def client(sess) -> None:
        while True:
            i = work.get()
            if i is None:
                return
            sent[i] = time.monotonic()
            try:
                res = sess.query(texts[sched["text_of"][i]])
                done[i] = time.monotonic()
                answers[i] = (list(res.vars), [list(r) for r in res.rows], res.n_total)
            except Exception as e:  # noqa: BLE001 — recorded, the run goes on
                done[i] = time.monotonic()
                error[i] = f"{type(e).__name__}: {e}"

    threads = [threading.Thread(target=client, args=(s,), daemon=True) for s in sessions]
    for t in threads:
        t.start()
    print("ready", flush=True)
    line = sys.stdin.readline().split()
    if len(line) != 2 or line[0] != "go":
        return 2
    t0 = float(line[1])
    for i in range(n):
        wait = t0 + due[i] - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        work.put(i)
    for _ in threads:
        work.put(None)
    deadline = t0 + (due[-1] if n else 0.0) + sched["drain_s"]
    for t in threads:
        t.join(timeout=max(0.0, deadline - time.monotonic()))
    for s in sessions:
        try:
            s.close()
        except OSError:
            pass
    # each distinct answer once per text, with how many requests got it
    distinct: dict = {}
    for i in range(n):
        if answers[i] is not None and error[i] is None:
            key = json.dumps(answers[i])
            per = distinct.setdefault(str(sched["text_of"][i]), {})
            per[key] = per.get(key, 0) + 1
    out = {
        "t0": t0,
        "sent": [None if s is None else s - t0 for s in sent],
        "done": [None if d is None or error[i] else d - t0 for i, d in enumerate(done)],
        "errors": [e for e in error if e],
        "answers": distinct,
    }
    with open(results_path, "w", encoding="utf-8") as f:
        json.dump(out, f)
    print("done", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2]))
