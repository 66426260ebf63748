"""Observability overhead benchmark: telemetry must be (nearly) free.

The ``repro.obs`` design invariant is that the device counters are
UNCONDITIONAL runtime state folded inside the already-jitted ingest —
so "telemetry on" vs "off" differs only in host-side work at existing
sync points (emissions, checkpoints), never in what XLA compiles.  This
benchmark measures both halves of that claim on the fused ingest hot
path (the ``bench_ingest`` configuration):

* ``obs.hot_loop.*`` — per-chunk latency of the jitted fused fold (the
  counters ride inside it), plus the structural checks: telemetry-on
  and -off executors both trace once, and their per-chunk jaxprs are
  string-identical.
* ``obs.sync_point.on_emission`` — median µs of ONE full telemetry
  sync-point visit (result summary, watermark/controller mirrors, two
  JSONL writes + flush) — telemetry's entire marginal cost, since the
  hot loop is structurally unchanged.  Derived ``overhead_pct``
  amortizes it over the emission period against the bare per-chunk
  cost: ``on_emission_us / (emit_every · chunk_us)`` — asserted
  ``< 3%`` on the pipelined fused path (the acceptance bar).  Both
  numerator and denominator are median/min micro-timings, so the
  verdict is reproducible on a noisy container where an end-to-end A/B
  (±8% run-to-run here) cannot resolve a ~1% true difference.
* ``obs.e2e.<mode>`` — the end-to-end A/B anyway (best of ``TRIALS``
  interleaved trials), informational: confirms the amortized number's
  scale, carries the container noise in ``derived``.

Writes ``BENCH_obs.json`` (to ``$BENCH_OUT`` or the CWD) in every lane —
the CI smoke job uploads it as the telemetry-cost trajectory artifact.
"""
from __future__ import annotations

import json
import os
import time

import jax
import numpy as np

from benchmarks.common import SMOKE, emit, param, time_call
from repro.obs import EventLog, Telemetry
from repro.runtime import (BatchedExecutor, PipelinedExecutor,
                           QueryRegistry, RuntimeConfig, init_state,
                           timestamped_stream)
from repro.runtime.executor import _ingest_chunk
from repro.stream import GaussianSource, StreamAggregator

NUM_STRATA = 3
OVERHEAD_BAR_PCT = 3.0
TRIALS = 7


def _registry():
    return QueryRegistry().register("total", "sum")


def _cfg(**kw):
    base = dict(num_strata=NUM_STRATA, capacity=128, num_intervals=8,
                interval_span=1.0, allowed_lateness=0.5, batch_chunks=4,
                emit_every=4, ingest="fused")
    base.update(kw)
    return RuntimeConfig(**base)


def _chunks(num_chunks, chunk_size, seed=3):
    agg = StreamAggregator(GaussianSource(), seed=seed)
    rate = chunk_size * num_chunks / 4.0
    return list(timestamped_stream(agg, chunk_size, num_chunks, rate))


def _wall(ex, chunks):
    t0 = time.perf_counter()
    for c in chunks:
        ex.push(c)
    ex.finalize()
    return time.perf_counter() - t0


def _e2e_pair(mode_cls, cfg, chunks, key, log_dir):
    """Best-of-TRIALS wall of telemetry-on vs -off runs, trials
    interleaved so machine drift hits both arms equally.  The telemetry
    arm ALSO writes a real JSONL file — the full production cost."""
    bare = mode_cls(cfg, _registry(), key)
    inst = mode_cls(cfg, _registry(), key)
    bare.run(chunks[:cfg.batch_chunks])          # warm compile (shared
    # Warm the instrumented arm THROUGH an emission with telemetry
    # attached, so the host path's own first-call costs (summary jits,
    # file-cache) land outside the timed trials too.
    with EventLog(os.path.join(log_dir, "warm.jsonl")) as warm_log:
        inst.attach_telemetry(Telemetry(warm_log))
        inst.run(chunks[:max(cfg.batch_chunks, cfg.emit_every)])
    walls = {"off": [], "on": []}
    for trial in range(TRIALS):
        bare.reset(key)
        walls["off"].append(_wall(bare, chunks))
        inst.reset(key)
        path = os.path.join(log_dir, f"{bare.mode}_t{trial}.jsonl")
        with EventLog(path) as log:
            inst.attach_telemetry(Telemetry(log))
            walls["on"].append(_wall(inst, chunks))
    off = min(walls["off"])
    on = min(walls["on"])
    return off, on, (on - off) / off * 100.0


def run() -> list:
    rows = []
    key = jax.random.PRNGKey(0)
    import tempfile
    log_dir = tempfile.mkdtemp(prefix="bench_obs_")
    report = {
        "meta": {"smoke": SMOKE, "jax_backend": jax.default_backend(),
                 "trials": TRIALS, "overhead_bar_pct": OVERHEAD_BAR_PCT},
        "hot_loop": {},
        "e2e": {},
    }

    # --- hot loop: fused fold latency + the structural free-ness proof --
    chunk_size = param(4096, 1024)
    cfg = _cfg()
    state = init_state(cfg, key)
    chunk = _chunks(1, chunk_size)[0]
    fold = jax.jit(lambda st, ch: _ingest_chunk(cfg, st, ch))
    us = time_call(fold, state, chunk, warmup=2, iters=7)
    rows.append(emit("obs.hot_loop.fused_fold", us,
                     f"items_per_sec={chunk_size / (us / 1e6):.0f}"))

    probe = _chunks(6, param(2048, 512))
    off_ex = PipelinedExecutor(_cfg(emit_every=10_000), _registry(), key)
    on_ex = PipelinedExecutor(_cfg(emit_every=10_000), _registry(), key,
                              telemetry=Telemetry(EventLog()))
    for c in probe:
        off_ex.push(c)
        on_ex.push(c)
    jx_off = str(jax.make_jaxpr(
        lambda st, ch: _ingest_chunk(cfg, st, ch))(off_ex.state, probe[0]))
    jx_on = str(jax.make_jaxpr(
        lambda st, ch: _ingest_chunk(cfg, st, ch))(on_ex.state, probe[0]))
    identical = (jx_on == jx_off and off_ex.trace_count == 1
                 and on_ex.trace_count == 1)
    assert identical, "telemetry changed the compiled hot loop!"
    report["hot_loop"] = {
        "fused_fold_us": us, "chunk_size": chunk_size,
        "jaxpr_identical": identical,
        "trace_count_on": on_ex.trace_count,
        "trace_count_off": off_ex.trace_count,
    }
    rows.append(emit("obs.hot_loop.jaxpr_identical", 0.0,
                     "telemetry-on == telemetry-off"))

    # --- sync-point cost: telemetry's entire marginal work, timed -----
    chunks = _chunks(param(96, 8), param(2048, 512))
    cfg = _cfg()
    ex = PipelinedExecutor(cfg, _registry(), key)
    ex.run(chunks)
    em = ex.emissions[-1]
    sync_log = EventLog(os.path.join(log_dir, "sync.jsonl"))
    tel = Telemetry(sync_log)
    ex.attach_telemetry(tel)

    def sync_point():
        tel.on_emission(ex, em)       # summary + mirrors + JSONL writes

    sync_us = time_call(sync_point, warmup=3, iters=31)
    rows.append(emit("obs.sync_point.on_emission", sync_us,
                     f"events_per_visit=2"))

    # Bare per-chunk cost (min over trials: noise only adds time).
    bare = PipelinedExecutor(cfg, _registry(), key)
    bare.run(chunks[:cfg.batch_chunks])
    bare_walls = []
    for _ in range(TRIALS):
        bare.reset(key)
        bare_walls.append(_wall(bare, chunks))
    chunk_us = min(bare_walls) / len(chunks) * 1e6
    pct = sync_us / (cfg.emit_every * chunk_us) * 100.0
    report["sync_point"] = {
        "on_emission_us": sync_us, "bare_chunk_us": chunk_us,
        "emit_every": cfg.emit_every, "overhead_pct": pct,
    }
    # The acceptance bar: full telemetry costs < 3% of the fused
    # pipelined path (the latency-critical one), amortized over the
    # emission period.  Full lane only — the smoke lane's toy chunks
    # shrink the denominator while the sync-point cost stays fixed, so
    # its ratio is meaningless (common.py's standing caveat).
    if not SMOKE:
        assert pct < OVERHEAD_BAR_PCT, (
            f"telemetry overhead {pct:.2f}% >= {OVERHEAD_BAR_PCT}% bar")
    rows.append(emit("obs.overhead_bar", 0.0,
                     f"pipelined={pct:.2f}%<{OVERHEAD_BAR_PCT}%"
                     + (";smoke_unchecked" if SMOKE else "")))

    # --- end to end A/B (informational: carries container noise) ------
    for name, cls in (("pipelined", PipelinedExecutor),
                      ("batched", BatchedExecutor)):
        off, on, e2e_pct = _e2e_pair(cls, _cfg(), chunks,
                                     jax.random.fold_in(key, 1), log_dir)
        report["e2e"][name] = {"off_s": off, "on_s": on,
                               "overhead_pct": e2e_pct}
        rows.append(emit(f"obs.e2e.{name}", on / len(chunks) * 1e6,
                         f"off_us={off / len(chunks) * 1e6:.1f};"
                         f"overhead_pct={e2e_pct:.2f}"))

    out_dir = os.environ.get("BENCH_OUT", ".")
    out_path = os.path.join(out_dir, "BENCH_obs.json")
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    print(f"# wrote {out_path}")
    return rows


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    run()
