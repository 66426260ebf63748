"""Ingest hot-path benchmark: single-pass fused ring fold vs the pre-PR
masked-vmap path.

The paper's value proposition is throughput (§5: 1.15×–3× over native at
80%–10% sampling). Before this PR the runtime *multiplied* ingest work by
the ring size: ``_ingest_chunk`` vmapped a reservoir fold over all K
interval slots with per-slot masks — K·M updates per M-item chunk. The
fused path routes each item once to its (slot, stratum) cell and folds
the chunk through ONE reservoir update. Both paths draw the chunk
uniforms from the ring's lead key, so their outputs are bit-identical
(asserted below) and the speedup is pure execution strategy.

Sections:
* fold-level:   jitted ``_ingest_chunk`` fused vs masked over K ∈
                {4, 8, 16} and a chunk-size sweep — the headline ≥2×@K=8 /
                ≥3×@K=16 acceptance numbers.
* one-kernel:   the PR-7 single-Pallas-call ingest vs the fused-jnp path,
                fold-level and end-to-end. Rows are labelled by execution
                mode, which the platform decides
                (``kernels/ops.interpret_mode``): ``interpret`` on a CPU
                backend (the Pallas emulator still traces to XLA under
                jit, so these are real CPU numbers, not a TPU claim) and
                ``compiled`` elsewhere. The lane that did not run is
                recorded as unavailable-with-reason — never fabricated.
* executor:     end-to-end items/s + emission step-latency p50/p99 for
                both modes (batched / pipelined), sharded and not, on the
                fused path with donated state buffers.

Writes ``BENCH_ingest.json`` (to ``$BENCH_OUT`` or the CWD) in every
lane — the ``--smoke`` CI job uploads it as the perf-trajectory artifact.
The written file is re-read and validated against ``_validate_report``'s
schema in every lane, so a refactor that silently drops a section fails
CI instead of shipping a hollow artifact.
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import SMOKE, emit, param, time_call
from repro.kernels import ops as kops
from repro.runtime import (BatchedExecutor, PipelinedExecutor,
                           QueryRegistry, RuntimeConfig, init_state,
                           stamp_sharded, timestamped_stream)
from repro.runtime.executor import _ingest_chunk
from repro.stream import GaussianSource, StreamAggregator

NUM_STRATA = 3                      # GaussianSource's A/B/C mixture


def _registry():
    return QueryRegistry().register("total", "sum")


def _cfg(k: int, ingest: str = "fused", shards: int = 1) -> RuntimeConfig:
    return RuntimeConfig(num_strata=NUM_STRATA, capacity=128,
                         num_intervals=k, interval_span=1.0,
                         allowed_lateness=0.5, num_shards=shards,
                         batch_chunks=4, emit_every=4, ingest=ingest)


def _chunks(num_chunks: int, chunk_size: int, seed: int = 3):
    agg = StreamAggregator(GaussianSource(), seed=seed)
    rate = chunk_size * num_chunks / 4.0      # stream spans ~4 intervals
    return list(timestamped_stream(agg, chunk_size, num_chunks, rate))


def _fold_pair(k: int, chunk_size: int, key):
    """Median per-chunk latency of the jitted fused and masked folds on
    identical inputs (no donation here — timing reuses the state)."""
    cfg_f, cfg_m = _cfg(k), _cfg(k, ingest="masked")
    state = init_state(cfg_f, key)
    chunk = _chunks(1, chunk_size)[0]
    fused = jax.jit(lambda st, ch: _ingest_chunk(cfg_f, st, ch))
    masked = jax.jit(lambda st, ch: _ingest_chunk(cfg_m, st, ch))
    us_f = time_call(fused, state, chunk, warmup=2, iters=7)
    us_m = time_call(masked, state, chunk, warmup=2, iters=7)
    return us_f, us_m


def _fold_onekernel(k: int, chunk_size: int, key) -> float:
    """Median per-chunk latency of the jitted one-shot-kernel ingest."""
    cfg = _cfg(k, ingest="onekernel")
    state = init_state(cfg, key)
    chunk = _chunks(1, chunk_size)[0]
    fn = jax.jit(lambda st, ch: _ingest_chunk(cfg, st, ch))
    return time_call(fn, state, chunk, warmup=2, iters=7)


def _assert_answers_identical(k: int, other: str, key) -> bool:
    """The ``other`` ingest path must emit answers bitwise-identical to
    fused — a speedup may not change a single bit of output."""
    chunks = _chunks(param(16, 8), param(2048, 512))
    ef = BatchedExecutor(_cfg(k), _registry(), key).run(chunks)
    eo = BatchedExecutor(_cfg(k, ingest=other), _registry(),
                         key).run(chunks)
    for a, b in zip(ef, eo):
        if not np.array_equal(np.asarray(a.results["total"].value),
                              np.asarray(b.results["total"].value)):
            raise AssertionError(
                f"fused/{other} emission answers diverged at K={k}")
    return True


def _executor_stats(mode_cls, cfg, chunks, key):
    """items/s + emission-latency percentiles for one executor run
    (warm pass first so trace+compile stays out of the timed region)."""
    ex = mode_cls(cfg, _registry(), key)
    # Warm exactly one full micro-batch/emission period so the timed
    # region re-pays neither trace+compile nor a ragged batch size.
    ex.run(chunks[:cfg.batch_chunks])
    ex.reset(key)
    t0 = time.perf_counter()
    emissions = ex.run(chunks)
    wall = time.perf_counter() - t0
    items = sum(int(c.values.size) for c in chunks)
    lats = np.asarray([e.latency_s for e in emissions], np.float64)
    return {
        "items_per_s": items / wall,
        "wall_s": wall,
        "emissions": len(emissions),
        "step_latency_p50_ms": float(np.percentile(lats, 50) * 1e3),
        "step_latency_p99_ms": float(np.percentile(lats, 99) * 1e3),
    }


def _require(cond: bool, path: str, why: str) -> None:
    if not cond:
        raise ValueError(f"BENCH_ingest.json schema: {path}: {why}")


def _validate_report(report: dict) -> None:
    """Small structural schema for the artifact (run in EVERY lane,
    including ``--smoke``): required keys present, numbers are finite
    numerics, mode/fold/one-kernel sections nonempty, the bitwise
    contracts asserted. Catches a refactor that silently drops a section
    before CI uploads a hollow JSON."""
    def num(d, key, path):
        _require(key in d, f"{path}.{key}", "missing")
        v = d[key]
        _require(isinstance(v, (int, float)) and not isinstance(v, bool)
                 and np.isfinite(v), f"{path}.{key}",
                 f"expected finite number, got {v!r}")

    for key in ("meta", "fold", "chunk_sweep_k8", "onekernel", "modes",
                "answers_identical", "onekernel_identical"):
        _require(key in report, key, "missing")
    meta = report["meta"]
    _require(isinstance(meta.get("smoke"), bool), "meta.smoke",
             "expected bool")
    _require(isinstance(meta.get("jax_backend"), str), "meta.jax_backend",
             "expected str")
    num(meta, "num_strata", "meta")
    num(meta, "capacity", "meta")
    _require(len(report["fold"]) > 0, "fold", "no rows")
    for name, row in report["fold"].items():
        for f in ("chunk_size", "fused_us", "masked_us", "speedup"):
            num(row, f, f"fold.{name}")
    _require(len(report["chunk_sweep_k8"]) > 0, "chunk_sweep_k8",
             "no rows")
    for i, row in enumerate(report["chunk_sweep_k8"]):
        for f in ("chunk_size", "fused_us", "masked_us", "speedup"):
            num(row, f, f"chunk_sweep_k8[{i}]")
    ok = report["onekernel"]
    ran = 0
    for lane in ("interpret", "compiled"):
        rows = ok.get(lane, {})
        if rows.get("available") is False:
            _require(isinstance(rows.get("reason"), str) and rows["reason"],
                     f"onekernel.{lane}.reason",
                     "unavailable lane must say why")
            continue
        ran += 1
        rows = {n: r for n, r in rows.items() if isinstance(r, dict)}
        _require(len(rows) > 0, f"onekernel.{lane}",
                 "no rows and no unavailable-reason")
        for name, row in rows.items():
            for f in ("chunk_size", "onekernel_us", "fused_us",
                      "speedup_vs_fused"):
                num(row, f, f"onekernel.{lane}.{name}")
    _require(ran == 1, "onekernel", "exactly one lane (the platform's) "
             "must carry rows")
    _require(len(report["modes"]) > 0, "modes", "no rows")
    for name, row in report["modes"].items():
        for f in ("items_per_s", "wall_s", "emissions",
                  "step_latency_p50_ms", "step_latency_p99_ms"):
            num(row, f, f"modes.{name}")
    for want in ("batched_onekernel", "pipelined_onekernel"):
        _require(want in report["modes"], f"modes.{want}", "missing")
    _require(report["answers_identical"] is True, "answers_identical",
             "fused/masked bitwise contract not asserted")
    _require(report["onekernel_identical"] is True, "onekernel_identical",
             "fused/onekernel bitwise contract not asserted")


def run() -> list:
    rows = []
    key = jax.random.PRNGKey(0)
    report = {
        "meta": {
            "smoke": SMOKE,
            "jax_backend": jax.default_backend(),
            "num_strata": NUM_STRATA,
            "capacity": 128,
        },
        "fold": {},
        "chunk_sweep_k8": [],
        "onekernel": {"interpret": {}, "compiled": {}},
        "modes": {},
        "answers_identical": False,
        "onekernel_identical": False,
    }

    # --- fold-level: the headline fused-vs-masked ratio per ring size ---
    chunk_size = param(4096, 1024)
    for k in (4, 8, 16):
        us_f, us_m = _fold_pair(k, chunk_size, key)
        speedup = us_m / us_f
        rows.append(emit(
            f"ingest.fold.fused.k{k}", us_f,
            f"items_per_sec={chunk_size / (us_f / 1e6):.0f}"))
        rows.append(emit(
            f"ingest.fold.masked.k{k}", us_m,
            f"speedup_fused={speedup:.2f}x"))
        report["fold"][f"k{k}"] = {
            "chunk_size": chunk_size,
            "fused_us": us_f,
            "masked_us": us_m,
            "speedup": speedup,
            "items_per_s_fused": chunk_size / (us_f / 1e6),
            "items_per_s_masked": chunk_size / (us_m / 1e6),
        }

    # --- chunk-size sweep at K=8 ---
    for m in (param(1024, 256), param(4096, 1024), param(16384, 2048)):
        us_f, us_m = _fold_pair(8, m, key)
        rows.append(emit(
            f"ingest.fold.fused.k8.m{m}", us_f,
            f"speedup_fused={us_m / us_f:.2f}x"))
        report["chunk_sweep_k8"].append(
            {"chunk_size": m, "fused_us": us_f, "masked_us": us_m,
             "speedup": us_m / us_f})

    # --- one-kernel ingest: single Pallas call vs the fused-jnp path ---
    def onekernel_lane(lane: str):
        for k in (4, 8):
            us_f, _ = _fold_pair(k, chunk_size, key)
            us_o = _fold_onekernel(k, chunk_size, key)
            rel = us_f / us_o       # >1 ⇒ the kernel wins
            rows.append(emit(
                f"ingest.fold.onekernel.{lane}.k{k}", us_o,
                f"vs_fused={rel:.3f}x "
                f"items_per_sec={chunk_size / (us_o / 1e6):.0f}"))
            report["onekernel"][lane][f"k{k}"] = {
                "chunk_size": chunk_size,
                "onekernel_us": us_o,
                "fused_us": us_f,
                "speedup_vs_fused": rel,
                "items_per_s_onekernel": chunk_size / (us_o / 1e6),
            }

    # The platform picks the lane (kernels/ops.interpret_mode); the
    # other one is recorded as unavailable, with the reason.
    backend = jax.default_backend()
    lane, other = (("interpret", "compiled") if kops.interpret_mode()
                   else ("compiled", "interpret"))
    onekernel_lane(lane)
    report["onekernel"][other] = {
        "available": False,
        "reason": f"jax backend is {backend!r}; the platform runs the "
                  f"{lane} lane"}

    # --- identical answers (the acceptance contract) ---
    report["answers_identical"] = _assert_answers_identical(
        8, "masked", key)
    rows.append(emit("ingest.answers_identical", 0.0,
                     "fused==masked bitwise"))
    report["onekernel_identical"] = _assert_answers_identical(
        8, "onekernel", key)
    rows.append(emit("ingest.onekernel_identical", 0.0,
                     "fused==onekernel bitwise"))

    # --- executor end-to-end: both modes, sharded and not ---
    n_chunks, m = param(24, 8), param(2048, 512)
    chunks = _chunks(n_chunks, m)
    agg = StreamAggregator(GaussianSource(), seed=5)
    per_shard = m // 4
    sharded_chunks = [
        stamp_sharded(agg.sharded_interval(e, 4, per_shard), e * 0.5,
                      per_shard / 0.5) for e in range(n_chunks)]
    for name, cls in (("batched", BatchedExecutor),
                      ("pipelined", PipelinedExecutor)):
        st = _executor_stats(cls, _cfg(8), chunks,
                             jax.random.fold_in(key, 1))
        report["modes"][name] = st
        rows.append(emit(
            f"ingest.mode.{name}",
            st["step_latency_p50_ms"] * 1e3,
            f"items_per_sec={st['items_per_s']:.0f} "
            f"p99_ms={st['step_latency_p99_ms']:.2f}"))
        st = _executor_stats(cls, _cfg(8, shards=4), sharded_chunks,
                             jax.random.fold_in(key, 2))
        report["modes"][f"{name}_sharded4"] = st
        rows.append(emit(
            f"ingest.mode.{name}.sharded4",
            st["step_latency_p50_ms"] * 1e3,
            f"items_per_sec={st['items_per_s']:.0f} "
            f"p99_ms={st['step_latency_p99_ms']:.2f}"))
        st = _executor_stats(cls, _cfg(8, ingest="onekernel"), chunks,
                             jax.random.fold_in(key, 3))
        report["modes"][f"{name}_onekernel"] = st
        rows.append(emit(
            f"ingest.mode.{name}.onekernel",
            st["step_latency_p50_ms"] * 1e3,
            f"items_per_sec={st['items_per_s']:.0f} "
            f"p99_ms={st['step_latency_p99_ms']:.2f}"))

    out_dir = os.environ.get("BENCH_OUT", ".")
    out_path = os.path.join(out_dir, "BENCH_ingest.json")
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    with open(out_path) as f:          # validate what actually landed
        _validate_report(json.load(f))
    print(f"# wrote {out_path} (schema OK)")
    return rows


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    run()
