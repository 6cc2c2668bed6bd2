#!/usr/bin/env python3
"""Find a serve cell's knee: offer rising fixed rates to one server in
one process and print, per rate, what was offered and what came back.

    python bench/sweep.py --workload <serve cell> --seed <n> --seconds <s> \
        --rates 100,200,400

Each rate runs the cell's schedule (``--seconds`` long) through a fresh
load generator against the same server.  A rate is kept up with when
the answers' throughput is at least 97% of the offered rate and the
median latency of the schedule's last fifth is at most twice that of
its first fifth (no backlog that grows through the window).  The knee is
the highest rate kept up with below the first that is not; the cell's
mix runs at four fifths of it.  The last line is
``{"knee_qps": ..., "rate_qps": <four fifths of it>, "rates": [...]}``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402
import run  # noqa: E402


def reading(state, win: dict, rate: float) -> dict:
    due = state.schedule.due.tolist()
    done = state.results["done"]
    lat = [(due[i], 1e3 * (d - due[i])) for i, d in enumerate(done) if d is not None]
    n = len(due)
    fifth = max(1, n // 5)
    first = [x for t, x in lat if t <= due[fifth - 1]]
    last = [x for t, x in lat if t >= due[n - fifth]]
    span = max(d for d in done if d is not None) - due[0] if lat else 0.0
    thru = len(lat) / span if span > 0 else 0.0
    med_first = statistics.median(first) if first else float("inf")
    med_last = statistics.median(last) if last else float("inf")
    return {
        "rate_qps": rate, "requests": n, "failed": win["failed"],
        "throughput_qps": thru,
        "p50_ms": win["end_to_end"].get("query_p50_ms"),
        "p95_ms": win["end_to_end"].get("query_p95_ms"),
        "late_p95_ms": harness.quantile(win["late_ms"], 95) if win["late_ms"] else None,
        "first_fifth_p50_ms": med_first, "last_fifth_p50_ms": med_last,
        "kept_up": bool(win["failed"] == 0 and thru >= 0.97 * rate
                        and med_last <= 2 * med_first),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated, rising")
    args = ap.parse_args(argv)
    rates = sorted(float(r) for r in args.rates.split(","))
    cell, kind, device = run.prepare(args.workload)
    ctx = run.context(cell, args.seed, args.seconds)
    t0 = time.perf_counter()
    state = kind.setup(ctx, rate=rates[-1])
    print(json.dumps({"setup_s": time.perf_counter() - t0}), flush=True)
    out = []
    knee = None
    try:
        for rate in rates:
            kind.stop_loadgen(state)
            state.schedule = kind.make_schedule(state.tb, cell.traffic, args.seed,
                                                args.seconds, rate)
            kind.warm(state.server, state.schedule, cell.traffic["shapes"],
                      cell.traffic["max_pad"])
            kind.start_loadgen(state)
            win = kind.window(state, args.seconds)
            r = reading(state, win, rate)
            r["wrong_answers"] = kind.check(state, win)[0][1]
            from repro.obs import get_registry

            r["busiest_batch_so_far"] = get_registry().gauge("serve.busiest_batch").value
            print(json.dumps(r), flush=True)
            out.append(r)
            if not r["kept_up"]:
                break
            knee = rate
    finally:
        kind.release(state)
    print(json.dumps({"knee_qps": knee, "rate_qps": round(0.8 * knee) if knee else None,
                      "device": device, "rates": out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
