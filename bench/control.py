#!/usr/bin/env python3
"""Readings of the correctness check under the control and the faults.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 \\
        --seconds 10 --faults keep_first,half_batch,...

Runs the cell at its own size on the chip once per (fault, seed), each with
the fault of ``bench/faults.py`` planted under the timed path, and prints
one JSON line per run: the fault, the seed, ``correct`` and every number
compared. ``--faults sound`` runs the cell with nothing planted. The limits
in the configuration files were set between these readings and those of
sound runs (``PERF.md``). The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--faults", required=True)
    args = ap.parse_args(argv)
    try:
        cell = run.prepare(args.workload)
    except run.Refused as exc:
        print(f"control: {exc}", file=sys.stderr)
        return 2
    import faults
    for name in args.faults.split(","):
        plant = faults.Hooks if name == "sound" else faults.ALL[name]
        for seed in (int(s) for s in args.seeds.split(",")):
            cfg, e2e, per_layer = cell[:3], [], []
            with plant() as hooks:
                result, rows = run.measure(*cfg, e2e, per_layer, seed,
                                           args.seconds, False, hooks=hooks)
            print(json.dumps({
                "fault": name, "seed": seed, "correct": result["correct"],
                "readings": {n: v for n, v, _ in rows}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
