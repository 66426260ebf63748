#!/usr/bin/env python3
"""Chip smoke run: drive the streaming engine's main path once on a TPU.

    python chip_smoke.py            # one chip
    python chip_smoke.py --mesh4    # the four-chip mesh phase, and only it

One chip:

* **kernels** — each Pallas kernel, compiled, at the deployment's widths
  against its ``kernels/ref.py`` oracle (the two reservoir kernels
  bitwise, the stats and histogram kernels within the tolerances of
  ``tests/test_kernels.py``);
* **main path** — the paper's network-traffic case study
  (``configs/streamapprox.NETWORK_TRAFFIC``): NetFlow events in 3 strata
  (TCP/UDP/ICMP, mix 0.85/0.13/0.02), 131,072 events per interval in
  8,192-event chunks, out of order within the allowed lateness, 60%
  sampling (26,215 reservoir slots per stratum), four standing queries
  (sum, mean, count, p99). ``PipelinedExecutor`` runs the fused ingest
  with the default fold (the compiled ``reservoir_fold`` on TPU), the
  jnp fold and the one-kernel ingest; ``BatchedExecutor`` runs once.
  All four must emit one (interval, answers, widths) sequence bitwise;
  counts and watermark accounting must equal a numpy reference over the
  same events; the obs counters must conserve every offered event;
* **exactly-once** — a checkpointed pipelined run is killed mid-stream,
  a fresh executor restores the serialized bytes and replays the suffix,
  and the index-deduplicated output must equal the uninterrupted run.

``--mesh4``: the same deployment at ``num_shards=4`` placed one shard
per chip (``placement="mesh"``), held bitwise to the single-device
``placement="vmap"`` oracle, with exactly one all-gather per emission and
the state spread over four devices.

Timings printed are smoke timings of one pass — compile seconds from
JAX's compile events, run seconds of a second, warm pass — not benchmark
numbers. The script refuses to run without a TPU. The last line of
standard output is one JSON object, ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))


class SmokeFailure(AssertionError):
    """A check of the smoke run did not hold."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


@dataclasses.dataclass(frozen=True)
class Scale:
    """Deployment size. The defaults are the network-traffic case study;
    smaller values only serve to rehearse the script on a CPU."""
    chunk: int = 8192                 # events per chunk
    chunks_per_interval: int = 16     # 131,072 events per interval
    intervals: int = 9                # generated; the first 8 close
    num_strata: int = 3
    sampling_fraction: float = 0.6
    disorder: float = 0.45            # < allowed_lateness: none dropped
    kernel_block: int = 512           # reservoir_fold item tile
    seed: int = 0

    @property
    def events_per_interval(self) -> int:
        return self.chunk * self.chunks_per_interval

    @property
    def capacity(self) -> int:
        return math.ceil(self.sampling_fraction * self.events_per_interval
                         / self.num_strata)

    @property
    def num_chunks(self) -> int:
        return self.intervals * self.chunks_per_interval


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or fetching a
    compiled program from the persistent cache), from its own events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration


def report(phase: str, compile_s: float, run_s: float, events: int) -> None:
    print(f"[smoke timing] {phase}: compile_s={compile_s:.3f} "
          f"run_s={run_s:.4f} events_per_s={events / run_s:.1f} "
          f"(events={events}; one pass, not a benchmark)")


def timed(clock: CompileClock, fn):
    """``(result, compile seconds of a cold call, wall of a warm call)``."""
    import jax
    c0 = clock.seconds
    jax.block_until_ready(fn())
    compile_s = clock.seconds - c0
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, compile_s, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# Phase 1: the four kernels, compiled, against their oracles.
# ---------------------------------------------------------------------------

def kernel_phase(sc: Scale, clock: CompileClock) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import oasrs
    from repro.kernels import ops as kops
    from repro.kernels import ref
    from repro.kernels import reservoir as rk
    from repro.stream import NetflowSource

    interpret = kops.interpret_mode()
    k, s, n, m = 4, sc.num_strata, sc.capacity, sc.chunk
    rng = np.random.default_rng(sc.seed)
    net = NetflowSource().chunk(jax.random.PRNGKey(sc.seed), m)
    pay = np.asarray(net.values, np.float32)
    sid = np.asarray(net.stratum_ids, np.int32)
    ua = rng.random(m, dtype=np.float32)
    us = rng.random(m, dtype=np.float32)
    mask = rng.random(m) < 0.95

    # reservoir_fold on the fused path's flattened [K·S] ring, part full.
    cells = rng.integers(0, k * s, m).astype(np.int32)
    counts = rng.integers(0, 2 * n, k * s).astype(np.int32)
    cap = np.full((k * s,), n, np.int32)
    values = rng.normal(size=(k * s, n)).astype(np.float32)
    args = [jnp.asarray(a) for a in (cells, pay, ua, us, mask, counts, cap,
                                     values)]
    (got_v, got_c), c_s, r_s = timed(clock, lambda: rk.reservoir_fold(
        *args, block_m=sc.kernel_block, interpret=interpret))
    want_v, want_c = ref.reservoir_fold_ref(cells, pay, ua, us, mask,
                                            counts, cap, values)
    check(np.array_equal(np.asarray(got_c), want_c),
          "reservoir_fold counts != reservoir_fold_ref")
    check(np.array_equal(np.asarray(got_v), want_v),
          "reservoir_fold values != reservoir_fold_ref")
    report(f"kernel reservoir_fold [{m}] -> [{k * s}, {n}]", c_s, r_s, m)

    # The jnp fold on the same inputs: bitwise the kernel, and the cost
    # per chunk the default TPU fold is weighed against.
    state = oasrs.OASRSState(values=args[7], counts=args[5],
                             capacity=args[6], key=jax.random.PRNGKey(0))
    fold = jax.jit(oasrs.apply_chunk_uniforms)
    got, c_s, r_s = timed(clock, lambda: fold(state, args[0], args[1],
                                              args[4], args[2], args[3]))
    check(np.array_equal(np.asarray(got.values), want_v)
          and np.array_equal(np.asarray(got.counts), want_c),
          "jnp fold != reservoir_fold_ref")
    report(f"jnp fold [{m}] -> [{k * s}, {n}]", c_s, r_s, m)

    # one_shot_ingest: a disordered chunk into a pre-loaded ring.
    times = rng.uniform(2.0, 3.2, m).astype(np.float32)
    kw = dict(max_time=np.float32(3.0), open_interval=np.int32(3),
              on_time=np.int32(7), late=np.int32(1), dropped=np.int32(2),
              chunks=np.int32(4), items=np.int32(50),
              slot_interval=np.array([0, 1, 2, 3], np.int32),
              adopt=np.full((s,), n, np.int32),
              counts=rng.integers(0, 2 * n, (k, s)).astype(np.int32),
              capacity=np.full((k, s), n, np.int32),
              values=rng.normal(size=(k, s, n)).astype(np.float32),
              counters=rng.integers(0, 9, (6, s)).astype(np.int32))
    geo = dict(span=1.0, allowed_lateness=0.5)
    jkw = {key: jnp.asarray(v) for key, v in kw.items()}
    out, c_s, r_s = timed(clock, lambda: rk.one_shot_ingest(
        jnp.asarray(times), jnp.asarray(sid), jnp.asarray(pay),
        jnp.asarray(mask), jnp.asarray(ua), jnp.asarray(us),
        interpret=interpret, **geo, **jkw))
    want = ref.one_shot_ingest_ref(times, sid, pay, mask, ua, us, **kw,
                                   **geo)
    for name in ("values", "counts", "capacity", "slot_interval",
                 "max_time", "open_interval", "on_time", "late", "dropped",
                 "chunks", "items", "counters"):
        check(np.array_equal(np.asarray(getattr(out, name)), want[name]),
              f"one_shot_ingest {name} != one_shot_ingest_ref")
    check(int(out.late) > 1 and int(out.dropped) > 2,
          "one_shot_ingest case exercised no late or dropped events")
    report(f"kernel one_shot_ingest [{m}] -> [{k}, {s}, {n}]", c_s, r_s, m)

    # stratified_stats / weighted_hist over the merged sample buffer.
    g = k * s
    buf = NetflowSource().chunk(jax.random.PRNGKey(sc.seed + 1), g * n)
    x = np.asarray(buf.values, np.float32)
    gid = np.repeat(np.arange(g, dtype=np.int32), n)
    live = rng.random(g * n) < 0.9
    jx, jg, jl = jnp.asarray(x), jnp.asarray(gid), jnp.asarray(live)
    got, c_s, r_s = timed(clock, lambda: kops.stratum_moments(
        jx, jg, g, mask=jl))
    want = blocked_oracle(lambda v, i, mk: ref.stratified_stats_ref(
        v, i, mk, g), x, gid, live)
    close(got, want, ("counts", "sums", "sumsqs"), 1e-4, 1e-3,
          "stratified_stats")
    report(f"kernel stratified_stats [{g * n}] -> [{g}]", c_s, r_s, g * n)

    w = np.repeat(rng.uniform(1.0, 3.0, g).astype(np.float32), n)
    edges = jnp.linspace(float(x.min()), float(x.max()), 33)
    got, c_s, r_s = timed(clock, lambda: kops.weighted_histogram(
        jx, jg, jnp.asarray(w), jl, edges, g))
    want = blocked_oracle(lambda v, i, wt, mk: ref.weighted_hist_ref(
        v, i, wt, mk, edges, g), x, gid, w, live)
    close(got, want, ("whist", "counts"), 1e-5, 1e-4, "weighted_hist")
    report(f"kernel weighted_hist [{g * n}] -> [{g}, 32]", c_s, r_s, g * n)


def blocked_oracle(oracle, *items, block: int = 256):
    """A ``kernels/ref.py`` reduction oracle evaluated per block of
    ``block`` items (mask last), its block results summed in float64.

    Same semantics as one flat call; but a flat f32 scatter-add over
    ~26k items per cell drifts by ~3e-4 relative from the exact sum,
    more than the kernels' tolerances, while 256-item blocks keep the
    oracle within ~3e-6 of it."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    pad = (-items[0].shape[0]) % block
    tiles = [jnp.asarray(np.pad(a, (0, pad)).reshape(-1, block))
             for a in items]
    outs = jax.jit(jax.vmap(oracle))(*tiles)
    return [np.asarray(o, np.float64).sum(axis=0) for o in outs]


def close(got, want, names, rtol, atol, kernel) -> None:
    import numpy as np
    for g, w, name in zip(got, want, names):
        g = np.asarray(g, np.float64)
        err = np.max(np.abs(g - w) / (atol + rtol * np.abs(w)))
        check(err <= 1.0, f"{kernel} {name}: |got - oracle| reaches "
              f"{err:.3g}x the tolerance (rtol={rtol}, atol={atol})")


# ---------------------------------------------------------------------------
# The deployment: stream, queries, configuration, numpy reference.
# ---------------------------------------------------------------------------

def _every_event(v):
    import jax.numpy as jnp
    return jnp.ones(v.shape, jnp.bool_)


def registry():
    from repro.runtime import QueryRegistry
    return (QueryRegistry()
            .register("sum", "sum")
            .register("mean", "mean")
            .register("count", "count", predicate=_every_event)
            .register("p99", "quantile", qs=(0.99,)))


def runtime_config(sc: Scale, **kw):
    from repro.runtime import RuntimeConfig
    base = dict(num_strata=sc.num_strata, capacity=sc.capacity,
                num_intervals=4, interval_span=1.0, allowed_lateness=0.5,
                emission="watermark")
    base.update(kw)
    return RuntimeConfig(**base)


def make_stream(sc: Scale, num_shards: int = 1):
    """Offset-addressable NetFlow stream; all chunks are made up front."""
    from repro.stream import NetflowSource, StreamAggregator
    from repro.stream.replay import ReplayableStream
    stream = ReplayableStream(
        aggregator=StreamAggregator(NetflowSource(), seed=sc.seed),
        chunk_size=sc.chunk // num_shards,
        rate=float(sc.events_per_interval // num_shards),
        num_shards=num_shards, disorder=sc.disorder,
        disorder_seed=sc.seed + 1)
    return stream, stream.prefix(sc.num_chunks)


@dataclasses.dataclass
class Reference:
    """Plain numpy walk of the stream's event-time semantics."""
    cumulative: list          # per chunk: (on_time, late, dropped) so far
    counts: dict              # interval -> accepted events
    sums: dict                # interval -> Σ accepted values (float64)
    closes: list              # intervals in closing order
    offered: int


def reference(chunks, span: float, lateness: float, k: int) -> Reference:
    import jax
    import numpy as np
    neg = np.float32(-3.0e38)
    frontier, open_iv = neg, 0
    on_time = late = dropped = offered = 0
    counts, sums, cumulative, closes = {}, {}, [], []
    emitted = -1
    for c in jax.device_get(chunks):
        t = np.asarray(c.times, np.float32)
        v = np.asarray(c.values, np.float64)
        m = np.asarray(c.mask, bool)
        offered += int(m.sum())
        wmark = frontier - np.float32(lateness)
        tgt = np.floor(t / np.float32(span)).astype(np.int64)
        new_open = max(open_iv, int(tgt[m].max())) if m.any() else open_iv
        accept = m & ~(t < wmark) & ~(tgt < new_open - k + 1)
        on_time += int(np.sum(accept & (tgt >= open_iv)))
        late += int(np.sum(accept & (tgt < open_iv)))
        dropped += int(np.sum(m & ~accept))
        for iv in np.unique(tgt[accept]):
            sel = accept & (tgt == iv)
            counts[int(iv)] = counts.get(int(iv), 0) + int(sel.sum())
            sums[int(iv)] = sums.get(int(iv), 0.0) + float(v[sel].sum())
        if m.any():
            frontier = np.float32(max(frontier, t[m].max()))
        open_iv = new_open
        closed = int(np.floor((frontier - np.float32(lateness))
                              / np.float32(span))) - 1
        while emitted < closed:
            emitted += 1
            closes.append(emitted)
        cumulative.append((on_time, late, dropped))
    return Reference(cumulative, counts, sums, closes, offered)


def drive(ex, chunks):
    """Push every chunk; returns ``[(emission, chunks pushed when it
    fired)]``, the position the accounting reference is read at."""
    out = []
    for c in chunks:
        ex.push(c)
        out += [(e, ex.chunks_pushed) for e in ex.emissions[len(out):]]
    ex.finalize()
    out += [(e, ex.chunks_pushed) for e in ex.emissions[len(out):]]
    return out


def fingerprint(emissions, accounting: bool = False):
    """Bytes of everything the bitwise contract covers, per emission."""
    import numpy as np
    rows = []
    for e in emissions:
        row = [e.index, e.interval]
        for name in sorted(e.results):
            r = e.results[name]
            row += [name] + [np.asarray(a).tobytes() for a in (
                r.value, r.variance, r.error_bound(0.95))]
        if accounting:
            row += [e.watermark, e.open_interval, e.on_time, e.late,
                    e.dropped, np.asarray(e.capacity).tobytes(), e.items]
        rows.append(row)
    return rows


def run_executor(clock, label, make, key, chunks, events):
    """Cold pass (compiles), reset, warm pass (timed); ``make(key)``
    builds the executor."""
    import jax
    c0 = clock.seconds
    ex = make(key)
    drive(ex, chunks)
    compile_s = clock.seconds - c0
    ex.reset(key)
    t0 = time.perf_counter()
    fired = drive(ex, chunks)
    jax.block_until_ready(ex.state)
    report(label, compile_s, time.perf_counter() - t0, events)
    return ex, fired


# ---------------------------------------------------------------------------
# Phase 2: the main path on one chip.
# ---------------------------------------------------------------------------

def main_path_phase(sc: Scale, clock: CompileClock):
    import jax
    import numpy as np
    from repro.obs import metrics as obm
    from repro.runtime import BatchedExecutor, PipelinedExecutor

    key = jax.random.PRNGKey(0)
    _, chunks = make_stream(sc)
    cfg = runtime_config(sc)
    ref = reference(chunks, cfg.interval_span, cfg.allowed_lateness,
                    cfg.num_intervals)
    events = sc.num_chunks * sc.chunk
    print(f"[smoke] main path: {sc.num_chunks} chunks x {sc.chunk} events "
          f"= {events} events, capacity {sc.capacity}/stratum, "
          f"{len(ref.closes)} intervals close")
    check(len(ref.closes) >= min(8, sc.intervals - 1),
          f"only {len(ref.closes)} intervals close")
    check(ref.cumulative[-1][1] > 0, "the stream produced no late events")

    runs = {
        "pipelined fused (default fold)": lambda k: PipelinedExecutor(
            cfg, registry(), k),
        "pipelined fused (jnp fold)": lambda k: PipelinedExecutor(
            dataclasses.replace(cfg, backend="jnp"), registry(), k),
        "pipelined onekernel": lambda k: PipelinedExecutor(
            dataclasses.replace(cfg, ingest="onekernel"), registry(), k),
        "batched fused (default fold)": lambda k: BatchedExecutor(
            cfg, registry(), k),
    }
    prints = {}
    first = None
    for label, make in runs.items():
        ex, fired = run_executor(clock, label, make, key, chunks, events)
        ems = [e for e, _ in fired]
        check([e.interval for e in ems] == ref.closes,
              f"{label}: emitted intervals {[e.interval for e in ems]} "
              f"!= closes {ref.closes}")
        for e, pushed in fired:
            check((e.on_time, e.late, e.dropped)
                  == ref.cumulative[pushed - 1],
                  f"{label}: interval {e.interval} accounting "
                  f"{(e.on_time, e.late, e.dropped)} != reference "
                  f"{ref.cumulative[pushed - 1]}")
            got = float(np.asarray(e.results["count"].value))
            check(round(got) == ref.counts[e.interval],
                  f"{label}: interval {e.interval} count {got} != "
                  f"{ref.counts[e.interval]}")
        ctr = obm.counters(ex.state.metrics)
        ingested = int(ctr["ingested"].sum())
        accepted = int(ctr["accepted"].sum())
        dropped = int(ctr["dropped"].sum())
        check(ref.offered == ingested == accepted + dropped,
              f"{label}: obs conservation offered {ref.offered} ingested "
              f"{ingested} accepted {accepted} dropped {dropped}")
        check(accepted == sum(ref.cumulative[-1][:2])
              and dropped == ref.cumulative[-1][2],
              f"{label}: obs accepted/dropped {accepted}/{dropped} != "
              f"reference {ref.cumulative[-1]}")
        prints[label] = fingerprint(ems)
        if first is None:
            first, first_fired = ems, fired
        else:
            check(prints[label] == prints[next(iter(prints))],
                  f"{label}: emissions differ bitwise from "
                  f"{next(iter(prints))}")
    for e in first:
        est = e.results["mean"]
        exact = ref.sums[e.interval] / ref.counts[e.interval]
        print(f"[smoke] interval {e.interval}: mean exact={exact!r} "
              f"estimate={float(np.asarray(est.value))!r} half_width_95="
              f"{float(np.asarray(est.error_bound(0.95)))!r}")
    return cfg, chunks, first_fired


# ---------------------------------------------------------------------------
# Phase 3: crash, restore from bytes, replay the suffix.
# ---------------------------------------------------------------------------

def exactly_once_phase(sc: Scale, clock: CompileClock, cfg, chunks,
                       reference) -> None:
    """``reference``: the uninterrupted run's ``[(emission, pushed)]``.
    The crash comes one chunk after the middle emission, so replay from
    the last checkpoint re-emits it and the dedupe is exercised."""
    import jax
    from repro.runtime import Checkpointer, PipelinedExecutor

    key = jax.random.PRNGKey(0)
    reference_ems = [e for e, _ in reference]
    crash_after = reference[len(reference) // 2][1] + 1
    c0 = clock.seconds
    t0 = time.perf_counter()
    victim = PipelinedExecutor(cfg, registry(), key)
    ck = Checkpointer(every_chunks=sc.chunks_per_interval)
    victim.checkpointer = ck
    ck.save(victim)
    for c in chunks[:crash_after]:
        victim.push(c)
    payload = ck.latest           # only these bytes survive the crash
    pre = list(victim.emissions)
    del victim
    fresh = PipelinedExecutor(cfg, registry(), jax.random.PRNGKey(99))
    ckpt = fresh.restore(payload)
    for c in chunks[ckpt.stream_offset:]:
        fresh.push(c)
    recovered = fresh.finalize()
    jax.block_until_ready(fresh.state)
    wall = time.perf_counter() - t0
    dedup = {}
    for e in pre + recovered:
        dedup.setdefault(e.index, e)
    merged = [dedup[i] for i in sorted(dedup)]
    check(0 < ckpt.stream_offset <= crash_after,
          f"checkpoint offset {ckpt.stream_offset} vs crash {crash_after}")
    check(len(recovered) > 0 and recovered[0].index <= len(pre),
          "recovery emitted nothing after the checkpoint")
    check([e.index for e in merged] == list(range(len(reference_ems))),
          f"emission indices {[e.index for e in merged]} after recovery")
    check(fingerprint(merged, accounting=True)
          == fingerprint(reference_ems, accounting=True),
          "recovered emissions differ bitwise from the uninterrupted run")
    print(f"[smoke] exactly-once: crash after chunk {crash_after}, "
          f"checkpoint at offset {ckpt.stream_offset}, "
          f"{len(payload)} bytes, {len(pre)} emissions before the crash, "
          f"{len(recovered)} emitted after the restore, {len(merged)} after dedupe")
    report("crash + restore + replay (compile included in run_s)",
           clock.seconds - c0, wall, sc.num_chunks * sc.chunk)


# ---------------------------------------------------------------------------
# --mesh4: four chips against the vmap oracle.
# ---------------------------------------------------------------------------

def mesh4_phase(sc: Scale, clock: CompileClock) -> None:
    import jax
    import jax.numpy as jnp
    from repro.obs import metrics as obm
    from repro.runtime import PipelinedExecutor

    w = 4
    key = jax.random.PRNGKey(0)
    _, chunks = make_stream(sc, num_shards=w)
    events = sc.num_chunks * sc.chunk
    makers, runs = {}, {}
    for placement in ("vmap", "mesh"):
        cfg = runtime_config(sc, num_shards=w, placement=placement)
        makers[placement] = functools.partial(PipelinedExecutor, cfg,
                                              registry())
        ex, fired = run_executor(
            clock, f"pipelined fused, {w} shards, placement={placement}",
            makers[placement], key, chunks, events)
        runs[placement] = (ex, [e for e, _ in fired])
    (ex_v, ems_v), (ex_m, ems_m) = runs["vmap"], runs["mesh"]
    check(len(ems_v) >= min(8, sc.intervals - 1),
          f"only {len(ems_v)} intervals closed")
    fp_v = fingerprint(ems_v, accounting=True)
    fp_m = fingerprint(ems_m, accounting=True)
    if fp_m != fp_v or differing_leaves(ex_v.state, ex_m.state):
        raise SmokeFailure(
            "mesh run differs bitwise from the vmap oracle: "
            + first_divergence(makers["vmap"], makers["mesh"], key, chunks))
    cv, cm = obm.counters(ex_v.state.metrics), obm.counters(ex_m.state.metrics)
    check(all(jnp.array_equal(cv[n], cm[n]) for n in cv),
          "mesh obs counters differ from the vmap oracle")
    devices = jax.devices()[:w]
    for leaf in jax.tree_util.tree_leaves(ex_m.state):
        held = {s.device for s in leaf.addressable_shards}
        check(held == set(devices) and leaf.shape[0] == w,
              f"a state leaf {leaf.shape} is held by {sorted(map(str, held))}"
              f", not one shard on each of {w} devices")
    jaxpr = str(jax.make_jaxpr(
        lambda s, j, b, t: ex_m._emit_interval_fn(s, j, b, t))(
            ex_m.state, jnp.int32(0), ex_m._emit_base_key, jnp.float32(0)))
    check(jaxpr.count("all_gather[") == 1,
          f"{jaxpr.count('all_gather[')} all_gathers in the emission")
    for prim in ("psum", "all_reduce", "ppermute", "all_to_all"):
        check(prim not in jaxpr, f"collective {prim} in the emission")
    print(f"[smoke] mesh4: {len(ems_m)} emissions and the final state "
          f"bitwise equal to the vmap oracle, 1 all_gather per emission, "
          f"state on {[str(d) for d in devices]}")


#: State leaves fed from the host's wall clock (the measured step latency
#: and the pressure derived from it): no two runs reproduce them.
MEASURED_LEAVES = (".ctrl.latency_ema", ".ctrl.pressure")


def differing_leaves(a, b) -> list:
    """Paths of the leaves at which two runtime states differ bitwise,
    the measured ones left out."""
    import jax
    import numpy as np
    la = jax.tree_util.tree_leaves_with_path(jax.device_get(a))
    lb = jax.tree_util.tree_leaves(jax.device_get(b))
    paths = [jax.tree_util.keystr(p) for p, _ in la]
    return [p for p, (_, x), y in zip(paths, la, lb)
            if p not in MEASURED_LEAVES and not np.array_equal(x, y)]


def first_divergence(make_a, make_b, key, chunks) -> str:
    """Replay two executors chunk by chunk and say where they first part:
    the state leaves after an ingest step, or the fields of an emission."""
    a, b = make_a(key), make_b(key)
    parts = ("name", "value", "variance", "bound")
    for i, c in enumerate(chunks):
        before = len(a.emissions)
        a.push(c)
        b.push(c)
        if len(a.emissions) != len(b.emissions):
            return (f"after chunk {i}: {len(a.emissions)} vs "
                    f"{len(b.emissions)} emissions")
        for ea, eb in zip(a.emissions[before:], b.emissions[before:]):
            fa, fb = fingerprint([ea])[0], fingerprint([eb])[0]
            if fa != fb:
                names = sorted(ea.results)
                fields = [f"{names[j // 4]}.{parts[j % 4]}"
                          for j in range(len(fa) - 2)
                          if fa[2 + j] != fb[2 + j]]
                return (f"emission {ea.index} (interval {ea.interval}, at "
                        f"chunk {i}) differs in {fields or 'its header'}")
        leaves = differing_leaves(a.state, b.state)
        if leaves:
            return f"state after chunk {i} differs in {leaves}"
    return "no divergence on a chunk-by-chunk replay"


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mesh4", action="store_true",
                    help="run only the four-chip mesh phase")
    args = ap.parse_args(argv)

    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}; run this script "
              "from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    # The TPU runtime otherwise keeps its logs in a fixed directory
    # outside the checkout.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    from repro.utils import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{dev.platform!r}); refusing to run on it", file=sys.stderr)
        return 1
    need = 4 if args.mesh4 else 1
    if len(devices) < need:
        print(f"chip_smoke: --mesh4 needs 4 chips, found {len(devices)}",
              file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    print(f"[smoke] device {dev.device_kind} x{len(devices)}, "
          f"jax {jax.__version__}, compile cache {cache}")
    clock = CompileClock()
    sc = Scale()

    if args.mesh4:
        phases = [("mesh4", lambda: mesh4_phase(sc, clock))]
    else:
        carry = {}

        def main_path():
            carry["run"] = main_path_phase(sc, clock)

        def exactly_once():
            check("run" in carry, "main path failed; nothing to replay")
            exactly_once_phase(sc, clock, *carry["run"])

        phases = [("kernels", lambda: kernel_phase(sc, clock)),
                  ("main path", main_path),
                  ("exactly-once", exactly_once)]
    failed = []
    t_all = time.perf_counter()
    for name, phase in phases:
        t0 = time.perf_counter()
        try:
            phase()
            print(f"[smoke] phase {name}: ok "
                  f"({time.perf_counter() - t0:.1f} s)")
        except Exception:           # report every phase, then fail
            traceback.print_exc()
            print(f"[smoke] phase {name}: FAILED", file=sys.stderr)
            failed.append(name)
    print(f"[smoke] total {time.perf_counter() - t_all:.1f} s, "
          f"compile {clock.seconds:.1f} s")
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
