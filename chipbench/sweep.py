#!/usr/bin/env python3
"""Run cells with changed traffic, in one process: the rate sweeps.

    python3 chipbench/sweep.py --seconds 15 --seed 11 \\
        tafeng.ingest add_rate=40000 -- valuedshopper.forget \\
        forget_interval_s=2

Each group is a workload and ``key=value`` overrides of its traffic
file; every group runs as one ``run.py`` run (its own set-up, window
and check), one after the other.  The knee and latency each cell's
traffic file holds as a number were read from such runs: an ingest run
offered far above capacity gives the knee as its ``events_per_s``; a
forget run with a long interval gives the forget latency.
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("groups", nargs="+")
    args = ap.parse_args(argv)
    groups, cur = [], []
    for tok in args.groups + ["--"]:
        if tok == "--":
            if cur:
                groups.append(cur)
            cur = []
        else:
            cur.append(tok)
    rc = 0
    for workload, *sets in groups:
        cfg, traffic, chips, bench = run.cell_spec(workload)
        for kv in sets:
            key, val = kv.split("=", 1)
            traffic[key] = json.loads(val)
        print(f"sweep: {workload} {sets}", flush=True)
        rc |= run.run(["--workload", workload, "--seed", str(args.seed),
                       "--seconds", str(args.seconds),
                       "--trace", str(args.trace)],
                      spec=(cfg, traffic, chips, bench))
    return rc


if __name__ == "__main__":
    sys.exit(main())
