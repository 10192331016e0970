#!/usr/bin/env python3
"""Readings of a cell's control: the reference with one stated guarantee
broken, put in the program's place and compared as the program is.

Usage: python3 bench/control.py --workload NAME --seed N [--seed N ...]

The traffic file's ``control`` names the reference arguments it changes.
Each seed prints one JSON line with the numbers compared and their
limits; a sound control reads above at least one limit.  The benchmark's
own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import checks  # noqa: E402
import harness  # noqa: E402


def readings(config: dict, traffic: dict, seed: int) -> dict:
    """The control's numbers compared against the reference, for ``seed``."""
    entry = harness.load_module("entries", traffic["entry"])
    override = {k: v for k, v in traffic["control"].items() if k != "why"}
    ck, cj = entry.reference_jobs(config, traffic, seed, traffic["seeds"],
                                  first=1000, **override)
    rk, rj = entry.reference_jobs(config, traffic, seed,
                                  traffic["reference_runs"])
    outs = checks.run_jobs(cj + rj)
    win = checks.as_window(traffic, ck, outs[:len(cj)])
    return checks.compare(traffic, win, rk, outs[len(cj):])


def main(argv=None) -> int:
    """Print the control's readings for each seed."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    args = ap.parse_args(argv)
    spec = harness.cell_spec(
        harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json")),
        args.workload)
    for seed in args.seed:
        got = readings(spec["config"], spec["traffic"], seed)
        print(json.dumps(dict(workload=args.workload, seed=seed,
                              control=spec["traffic"]["control"],
                              checks=got)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
