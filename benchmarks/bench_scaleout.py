"""Scale-out benchmark: throughput vs shard count + rescale timeline.

Two claims from the deployment story get numbers here:

* ``scaleout.throughput.*`` — ingest throughput (items/s, pipelined
  executor) as the same total stream is split over 1/2/4/8 reservoir
  shards, for the vmap oracle placement and — when the process has
  enough devices (``XLA_FLAGS=--xla_force_host_platform_device_count=8``
  on CPU) — the real ``placement="mesh"`` deployment.  Mesh rows are
  skipped (and marked in the artifact) when devices are missing, so the
  module still runs in a default single-device lane.
* ``scaleout.rescale.*`` — the elastic path under sustained traffic: a
  4 -> 8 -> 4 schedule where each boundary does
  capture -> ``checkpoint.migrate`` -> serialize -> restore into the
  next width's warm executor.  The timeline records per-boundary
  capture/migrate/restore wall times and payload size, and asserts the
  emission indices stay contiguous across both rescales (the
  exactly-once continuity the crash harness proves bitwise).

Writes schema-validated ``BENCH_scaleout.json`` (to ``$BENCH_OUT`` or
the CWD) in every lane — a CI artifact alongside BENCH_ingest.
"""
from __future__ import annotations

import json
import os
import time

import jax
import numpy as np

from benchmarks.common import SMOKE, emit, param
from repro.runtime import (PipelinedExecutor, QueryRegistry,
                           RuntimeConfig)
from repro.runtime import checkpoint as ckp
from repro.stream import GaussianSource, StreamAggregator
from repro.stream.replay import ReplayableStream

SHARD_COUNTS = (1, 2, 4, 8)


def _registry():
    return QueryRegistry().register("total", "sum")


def _cfg(w, placement="vmap"):
    return RuntimeConfig(num_strata=3, capacity=64, num_intervals=4,
                         interval_span=1.0, allowed_lateness=0.5,
                         num_shards=w, placement=placement,
                         emit_every=8)


def _stream(w, per_shard, num_chunks):
    # Equal TOTAL arrival volume and the same event-time ramp at every
    # width: per-shard chunk size shrinks as shards grow.
    rate = per_shard * num_chunks / 4.0
    return ReplayableStream(
        aggregator=StreamAggregator(GaussianSource(), seed=7),
        chunk_size=per_shard, rate=rate, num_shards=w)


def _slot_width(ex):
    leaf = jax.tree_util.tree_leaves(ex.state.window.intervals.values)[0]
    return int(leaf.shape[3] if ex.cfg.num_shards > 1 else leaf.shape[2])


def _throughput(ex, chunks, key):
    ex.run(chunks[: max(ex.cfg.emit_every, 2)])      # warm compile
    ex.reset(key)
    t0 = time.perf_counter()
    ex.run(chunks)
    wall = time.perf_counter() - t0
    items = sum(int(c.values.size) for c in chunks)
    return items / wall, wall, items


def _rescale_timeline(placement, total_per_chunk, seg_chunks, key):
    """Drive 4 -> 8 -> 4 under traffic; time each boundary's phases."""
    widths = (4, 8, 4)
    executors = {w: PipelinedExecutor(_cfg(w, placement), _registry(),
                                      jax.random.fold_in(key, w))
                 for w in (4, 8)}
    streams = {w: _stream(w, total_per_chunk // w, seg_chunks * 3)
               for w in (4, 8)}
    ex = executors[widths[0]]
    ex.reset(key)
    emissions, timeline, offset = [], [], 0
    for i, w in enumerate(widths):
        for e in range(offset, offset + seg_chunks):
            ex.push(streams[w].chunk_at(e))
        offset += seg_chunks
        if i == len(widths) - 1:
            emissions += ex.finalize()
            break
        emissions += list(ex.emissions)
        w_next = widths[i + 1]
        nxt = executors[w_next]
        t0 = time.perf_counter()
        snap = ckp.capture(ex)
        t1 = time.perf_counter()
        payload = ckp.to_bytes(ckp.migrate(
            snap, w_next, new_max_capacity=_slot_width(nxt)))
        t2 = time.perf_counter()
        nxt.restore(ckp.from_bytes(payload, nxt.state))
        t3 = time.perf_counter()
        timeline.append({
            "boundary_offset": offset, "from_shards": w,
            "to_shards": w_next, "capture_ms": (t1 - t0) * 1e3,
            "migrate_ms": (t2 - t1) * 1e3,
            "restore_ms": (t3 - t2) * 1e3,
            "payload_bytes": len(payload),
        })
        ex = nxt
    indices = [e.index for e in emissions]
    return timeline, indices


def _require(cond: bool, path: str, why: str) -> None:
    if not cond:
        raise ValueError(f"BENCH_scaleout.json schema: {path}: {why}")


def _validate_report(report: dict) -> None:
    """Structural schema, run in EVERY lane (smoke included): required
    sections present, numbers finite, the throughput table covers every
    shard count, the rescale timeline has both boundaries and contiguous
    emission indices.  Catches a refactor that ships a hollow JSON."""
    def num(d, key, path):
        _require(key in d, f"{path}.{key}", "missing")
        v = d[key]
        _require(isinstance(v, (int, float)) and not isinstance(v, bool)
                 and np.isfinite(v), f"{path}.{key}",
                 f"expected finite number, got {v!r}")

    for key in ("meta", "throughput_vs_shards", "rescale"):
        _require(key in report, key, "missing")
    meta = report["meta"]
    _require(isinstance(meta.get("smoke"), bool), "meta.smoke",
             "expected bool")
    _require(isinstance(meta.get("devices"), int), "meta.devices",
             "expected int")
    rows = report["throughput_vs_shards"]
    _require(isinstance(rows, list) and rows, "throughput_vs_shards",
             "expected nonempty list")
    seen = set()
    for i, row in enumerate(rows):
        path = f"throughput_vs_shards[{i}]"
        for k in ("num_shards", "placement"):
            _require(k in row, f"{path}.{k}", "missing")
        if row.get("skipped"):
            continue
        num(row, "items_per_s", path)
        num(row, "wall_s", path)
        seen.add((row["num_shards"], row["placement"]))
    for w in SHARD_COUNTS:
        _require((w, "vmap") in seen or w == 1 and (1, "vmap") in seen,
                 f"throughput_vs_shards", f"no vmap row for {w} shards")
    res = report["rescale"]
    _require(isinstance(res.get("timeline"), list)
             and len(res["timeline"]) == 2, "rescale.timeline",
             "expected the two 4->8->4 boundaries")
    for i, b in enumerate(res["timeline"]):
        path = f"rescale.timeline[{i}]"
        for k in ("capture_ms", "migrate_ms", "restore_ms",
                  "payload_bytes"):
            num(b, k, path)
    _require(res.get("indices_contiguous") is True,
             "rescale.indices_contiguous",
             "emission indices broke across a rescale boundary")


def run() -> list:
    rows = []
    key = jax.random.PRNGKey(0)
    devices = len(jax.devices())
    report = {
        "meta": {"smoke": SMOKE, "jax_backend": jax.default_backend(),
                 "devices": devices},
        "throughput_vs_shards": [],
        "rescale": {},
    }

    total_per_chunk = param(8192, 1024)
    num_chunks = param(48, 8)
    for w in SHARD_COUNTS:
        stream = _stream(w, total_per_chunk // w, num_chunks)
        chunks = stream.prefix(num_chunks)
        placements = ["vmap"] if w == 1 else ["vmap", "mesh"]
        for placement in placements:
            name = f"scaleout.throughput.w{w}.{placement}"
            if placement == "mesh" and devices < w:
                report["throughput_vs_shards"].append(
                    {"num_shards": w, "placement": placement,
                     "skipped": f"needs {w} devices, have {devices}"})
                rows.append(emit(name, 0.0, "skipped=no_devices"))
                continue
            ex = PipelinedExecutor(_cfg(w, placement), _registry(),
                                   jax.random.fold_in(key, w))
            ips, wall, items = _throughput(ex, chunks, key)
            report["throughput_vs_shards"].append(
                {"num_shards": w, "placement": placement,
                 "items_per_s": ips, "wall_s": wall, "items": items})
            rows.append(emit(name, wall / num_chunks * 1e6,
                             f"items_per_sec={ips:.0f}"))

    rescale_placement = "mesh" if devices >= 8 else "vmap"
    timeline, indices = _rescale_timeline(
        rescale_placement, param(4096, 512), param(12, 4), key)
    contiguous = indices == list(range(len(indices)))
    report["rescale"] = {
        "placement": rescale_placement,
        "schedule": "4->8->4",
        "timeline": timeline,
        "emissions": len(indices),
        "indices_contiguous": contiguous,
    }
    for b in timeline:
        rows.append(emit(
            f"scaleout.rescale.{b['from_shards']}to{b['to_shards']}",
            b["migrate_ms"] * 1e3,
            f"capture_ms={b['capture_ms']:.1f};"
            f"restore_ms={b['restore_ms']:.1f};"
            f"payload_kb={b['payload_bytes'] / 1024:.0f}"))
    assert contiguous, "emission indices broke across a rescale"

    out_dir = os.environ.get("BENCH_OUT", ".")
    out_path = os.path.join(out_dir, "BENCH_scaleout.json")
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
