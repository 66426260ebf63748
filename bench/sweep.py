#!/usr/bin/env python3
"""Open-loop rate sweep of a cell, to find the highest rate it sustains.

    python3 bench/sweep.py --workload taxi.rate --seed 5 --seconds 30 \\
        --rates 40000,44000,48400,53240,58564,64420

(steps of 10%; run it on two or three seeds).

One process sets the cell up once, then offers each rate in turn for
``--seconds`` (the executor reset between rates) and prints one JSON line
per rate: result latency p50/p95, how late the generator ran (p50/p99, and
its mean over the first and the last quarter of the window), the longest
push, and ``backlog``: the last quarter ran later than the first by more
than ``BACKLOG_MS``, so the queue grew through the step. The highest rate
before the first backlog is the knee; the cell's traffic file takes 0.8 of
it, by hand. The benchmark's runs never search for a rate.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys

import run

#: Growth of the generator's lateness, first quarter of a step to its
#: last, that counts as a growing backlog (sound steps stay within about
#: one emission of their first quarter).
BACKLOG_MS = 100.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    try:
        cell, config, traffic, _, _ = run.prepare(args.workload)
    except run.Refused as exc:
        print(f"sweep: {exc}", file=sys.stderr)
        return 2
    import jax
    import numpy as np
    import deploy
    import gen as gen_mod
    import stats

    g = gen_mod.Generator(config, traffic, args.seed)
    key = jax.random.PRNGKey(np.uint32(args.seed % 2**32))
    ex = deploy.executor(config, key)
    e = 0
    while len(ex.emissions) < 2:
        ex.push(deploy.chunk(*g.at(e)))
        e += 1
    for rate in (float(r) for r in args.rates.split(",")):
        ex.reset(key)
        g.period = g.events_per_chunk / rate
        win = run.drive(ex, g, args.seconds)
        late = win.gen_late_ms
        q = max(len(late) // 4, 1)
        first, last = statistics.fmean(late[:q]), statistics.fmean(late[-q:])
        print(json.dumps({
            "rate_events_per_s": rate, "closes": len(win.emissions),
            "latency_p50_ms": stats.percentile(win.latency_ms, 50),
            "latency_p95_ms": stats.percentile(win.latency_ms, 95),
            "gen_late_p50_ms": stats.percentile(late, 50),
            "gen_late_p99_ms": stats.percentile(late, 99),
            "gen_late_first_quarter_ms": first,
            "gen_late_last_quarter_ms": last,
            "max_push_ms": max((b - a) * 1e3 for a, b, _, _ in win.pushes),
            "backlog": last - first > BACKLOG_MS}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
