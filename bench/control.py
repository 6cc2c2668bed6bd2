#!/usr/bin/env python3
"""Readings of a cell's correctness check: the program's and the
control's, seed after seed in one process.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed the cell runs as ``run.py`` runs it (set-up, a window of
``--seconds``, the program stopped) and prints one line with the check's
readings for what the program produced and for the control put in its
place: the plain reference with one guarantee of the configuration
broken (see each kind's ``control``).  Limits are set between the two.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell, kind, device = run.prepare(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = run.context(cell, seed, args.seconds)
        state = kind.setup(ctx)
        try:
            win = kind.window(state, args.seconds)
        finally:
            kind.release(state)
        program = {n: v for n, v, _l in kind.check(state, win)}
        control = {n: v for n, v, _l in kind.control(state)}
        print(json.dumps({"workload": cell.name, "seed": seed, "device": device["kind"],
                          "attempted": win["attempted"], "program": program,
                          "control": control}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
