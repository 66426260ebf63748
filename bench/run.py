#!/usr/bin/env python3
"""Benchmark of the approximate stream engine on the TPU, one cell per run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration (``bench/configs/<name>.json``: the deployment, its queries,
guarantees and the limits of the correctness check) and a traffic mix
(``bench/traffic/<name>.json``: loop, rate, chunk, disorder). The run

1. refuses without a TPU or with fewer chips than the cell asks for;
2. sets up: imports, draws the generator's pool from ``--seed``, builds
   the executor, compiles the cell's own step and emission programs (JAX's
   persistent cache at ``bench/.jax_cache``) and warms them by closing two
   intervals, then resets the executor: that is ``setup_s``;
3. measures for ``--seconds``: pushes host-resident chunks through
   ``PipelinedExecutor.push`` (closed loop: the next as soon as ``push``
   returns; open loop: each at its due time), reading every emission's
   answers back to the host;
4. compares what the window produced with the numpy reference
   (``bench/check.py``) and prints each number beside its limit;
5. prints one JSON line: the cell's end-to-end metrics (``--trace 0``) or
   its per-layer metrics (``--trace 1``, from a profiler trace of the
   window and the benchmark's own spans; each is a reader in
   ``bench/metrics/<name>.py``).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import glob
import importlib.util
import json
import math
import os
import shutil
import statistics
import sys
from typing import Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(HERE, ".jax_cache")
TRACE_DIR = os.path.join(HERE, ".trace")
#: Longest window a ``--trace 1`` run traces; its per-layer metrics come
#: from these seconds.
TRACE_SECONDS = 10.0


class Refused(Exception):
    """The run cannot measure this cell here; nothing is printed."""


def load_cell(name: str):
    path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.exists(path):
        raise Refused(f"no BENCHMARK.json at {ROOT}")
    with open(path) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Refused(f"unknown workload {name!r}; one of {sorted(cells)}")
    cell = cells[name]
    with open(os.path.join(HERE, "configs", cell["config"] + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    return cell, config, traffic, e2e, per_layer


class Window:
    """What one measured window did: per push ``(start, end, closes,
    due)`` on the benchmark's clock, and per emission its record."""

    def __init__(self):
        self.pushes = []
        self.emissions = []
        self.latency_ms = []
        self.gen_late_ms = []
        self.t0 = self.t1 = 0.0
        self.chunks = 0


def drive(ex, gen, seconds: float, hooks=None, trace_dir=None) -> Window:
    """The measured window (``hooks`` plant a fault; tests and the control
    runs use them, the benchmark's runs never do)."""
    import jax
    import deploy
    win = Window()
    due_of = gen.due
    profiler = jax.profiler
    if trace_dir is not None:
        opts = profiler.ProfileOptions()
        opts.python_tracer_level = 0
        profiler.start_trace(trace_dir, profiler_options=opts)
    win.t0 = time.perf_counter()
    deadline = win.t0 + seconds
    # An open loop that falls this far behind stops offering load.
    give_up = deadline + seconds
    e = 0
    while True:
        now = time.perf_counter()
        due = due_of(e)
        if due is None:
            if now >= deadline:
                break
        else:
            due += win.t0
            if due >= deadline or now >= give_up:
                break
            while now < due:
                if due - now > 2e-3:
                    time.sleep(due - now - 1e-3)
                now = time.perf_counter()
            win.gen_late_ms.append((now - due) * 1e3)
        with profiler.TraceAnnotation("bench.push"):
            start = time.perf_counter()
            chunk = deploy.chunk(*gen.at(e))
            if hooks is not None:
                chunk = hooks.chunk(chunk)
            before = len(ex.emissions)
            ex.push(chunk)
            new = ex.emissions[before:]
        if new:
            with profiler.TraceAnnotation("bench.read"):
                answers = jax.device_get([em.results for em in new])
        end = time.perf_counter()
        e += 1
        for em, res in zip(new, answers if new else ()):
            if hooks is not None:
                res = hooks.results(res)
            win.emissions.append({
                "interval": em.interval, "pushed": e,
                "on_time": em.on_time, "late": em.late,
                "dropped": em.dropped, "results": res})
            if due is not None:
                win.latency_ms.append((end - due) * 1e3)
        win.pushes.append((start, end, len(new), due))
    jax.block_until_ready(ex.state)
    win.t1 = time.perf_counter()
    win.chunks = e
    if trace_dir is not None:
        profiler.stop_trace()
    return win


def end_to_end(name: str, win: Window, gen, setup_s: float) -> float:
    import stats
    if name == "setup_s":
        return setup_s
    if name == "events_per_s":
        return win.chunks * gen.events_per_chunk / (win.t1 - win.t0)
    if name == "latency_p50_ms":
        return stats.percentile(win.latency_ms, 50)
    if name == "latency_p95_ms":
        return stats.percentile(win.latency_ms, 95)
    if name == "ci_half_width_pct":
        pct = [100.0 * 2.0 * math.sqrt(max(float(r.variance), 0.0))
               / abs(float(r.value))
               for r in (em["results"]["mean"] for em in win.emissions)]
        return statistics.fmean(pct) if pct else math.nan
    raise KeyError(f"no end-to-end metric {name!r}")


def local_module(relpath: str):
    """A module of the benchmark, by its path under ``bench/`` (names
    such as ``trace`` would otherwise find the standard library's)."""
    path = os.path.join(HERE, relpath + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_" + relpath.replace("/", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metric(name: str, ctx) -> Optional[float]:
    """The per-layer metric ``name``, read by ``bench/metrics/<name>.py``;
    ``None`` where it finds nothing to read."""
    return local_module("metrics/" + name).read(ctx)


class Context:
    """What a per-layer reader may read: the window, its trace, the cell."""

    def __init__(self, cell, config, traffic, win, gen, trace, peaks,
                 device_kind):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.window, self.gen, self.trace, self.peaks = win, gen, trace, peaks
        self.device_kind = device_kind


def measure(cell, config, traffic, e2e, per_layer, seed: int,
            seconds: float, trace: bool, hooks=None, log=print):
    """Set up, run the window, check it, and return the result line and
    the compared numbers."""
    import jax
    import numpy as np
    import check
    import deploy
    import gen as gen_mod
    import reference

    clock = reference.CompileClock()
    gen = gen_mod.Generator(config, traffic, seed)
    key = jax.random.PRNGKey(np.uint32(int(seed) % 2**32))
    ex = deploy.executor(config, key)
    if hooks is not None:
        hooks.executor(ex)
    e = 0
    while len(ex.emissions) < 2:
        ex.push(deploy.chunk(*gen.at(e)))
        e += 1
    ex.reset(key)
    jax.block_until_ready(ex.state)
    warm_traces, warm_compiles = deploy.traces(ex), clock.compiles
    setup_s = time.perf_counter() - T_START
    log(f"[bench] set-up {setup_s:.3f} s, compile {clock.seconds:.3f} s, "
        f"{clock.compiles} compiles", file=sys.stderr)

    trace_dir = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        trace_dir = TRACE_DIR
        seconds = min(seconds, TRACE_SECONDS)
    win = drive(ex, gen, seconds, hooks, trace_dir)
    retraces = (deploy.traces(ex) - warm_traces
                + clock.compiles - warm_compiles)
    devices = jax.devices()
    used = devices[:int(config["chips"])]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    ring, counters = deploy.ring(ex), deploy.counters(ex)
    del ex

    # The reference, over every chunk the window pushed (and, in an open
    # loop, every chunk that fell due in it).
    t_ref = time.perf_counter()
    ref = reference.Reference(int(config["num_shards"]),
                              int(config["num_strata"]),
                              float(config["interval_span"]),
                              float(config["allowed_lateness"]),
                              int(config["ring_intervals"]))
    for i in range(win.chunks):
        ref.feed(*gen.at(i))
    readings = check.compare(config, ref, win.emissions, ring, counters,
                             retraces)
    ok, rows = check.verdict(readings, config.get("limits", {}))
    attempted = len(ref.closes)
    if gen.period is not None:
        i = win.chunks
        while gen.due(i) < seconds:
            ref.feed(*gen.at(i))
            i += 1
        attempted = len(ref.closes)
    failed = max(0, attempted - len(win.emissions))
    log(f"[bench] window {win.t1 - win.t0:.3f} s, {win.chunks} chunks, "
        f"{len(win.emissions)} closes; reference {time.perf_counter() - t_ref:.3f} s",
        file=sys.stderr)

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    metrics = {}
    result = {"correct": ok and failed == 0, "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        trace_mod = local_module("trace")
        files = glob.glob(os.path.join(TRACE_DIR, "**", "*.xplane.pb"),
                          recursive=True)
        tr = trace_mod.reduce(files[0], num_devices=len(used)) if files \
            else None
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        with open(os.path.join(HERE, "peaks.json")) as f:
            peaks = json.load(f)
        ctx = Context(cell, config, traffic, win, gen, tr, peaks,
                      dev.device_kind)
        for m in per_layer:
            v = read_metric(m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if tr is not None:
            device["busy_s"] = tr.busy_s
            device["window_s"] = tr.window_s
            result["breakdown"] = tr.breakdown()
            log(f"[bench] trace matched {tr.matched}", file=sys.stderr)
    else:
        for m in e2e:
            metrics[m["name"]] = {"value": end_to_end(m["name"], win, gen,
                                                      setup_s),
                                  "unit": m["unit"]}
    rows.append(("failed_closes", float(failed), 0.0))
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in rows}
    return result, rows


def prepare(workload: str):
    """Load the cell, put the program on the path, point JAX's persistent
    cache at ``bench/.jax_cache`` and refuse without the chips the cell
    needs. Returns what ``measure`` takes before the seed."""
    cell, config, traffic, e2e, per_layer = load_cell(workload)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise Refused(f"no program (repro package) under {src}")
    sys.path[:0] = [src, HERE, os.path.join(HERE, "metrics")]
    # The TPU runtime otherwise logs to a fixed directory outside the
    # checkout.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Refused(f"no TPU: JAX's first device is "
                      f"{devices[0].platform!r}")
    if len(devices) < int(cell["chips"]):
        raise Refused(f"the cell needs {cell['chips']} chips, "
                      f"found {len(devices)}")
    return cell, config, traffic, e2e, per_layer


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = prepare(args.workload)
    except Refused as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    result, rows = measure(*cell, args.seed, args.seconds, bool(args.trace))
    for name, value, limit in rows:
        print(f"check {name} = {value!r} (limit {limit!r})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
