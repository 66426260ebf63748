"""Runtime subsystem tests: registry shared pass, watermark routing,
controller feedback, executor end-to-end, sharded ingest contract."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import adaptive
from repro.core import distributed as dist
from repro.core import error as err
from repro.core import oasrs
from repro.core import window as win
from repro.runtime import (BatchedExecutor, ControllerConfig,
                           PipelinedExecutor, QueryRegistry, RuntimeConfig,
                           controller as ctl, init_state, records,
                           registry as reg_mod, stamp, stamp_sharded,
                           timestamped_stream, watermark as wmk)
from repro.runtime.executor import _ingest_chunk
from repro.stream import GaussianSource, StreamAggregator

SPEC = jax.ShapeDtypeStruct((), jnp.float32)


def _registry():
    return (QueryRegistry()
            .register("total", "sum")
            .register("avg", "mean")
            .register("big", "count", predicate=lambda x: x > 500.0)
            .register("hist", "histogram", edges=(0.0, 100.0, 5000.0, 2e4))
            .register("p", "quantile", qs=(0.5, 0.9), num_replicates=8)
            .register("top", "heavy_hitters", k=4)
            .register("nuniq", "distinct", num_replicates=8))


def _cfg(**kw):
    base = dict(num_strata=3, capacity=128, num_intervals=4,
                interval_span=1.0, allowed_lateness=0.5,
                batch_chunks=4, emit_every=4)
    base.update(kw)
    return RuntimeConfig(**base)


def _chunks(num_chunks=16, chunk_size=512, seed=3):
    agg = StreamAggregator(GaussianSource(), seed=seed)
    # rate such that one interval == num_chunks/4 chunks (4 intervals).
    rate = chunk_size * num_chunks / 4.0
    return list(timestamped_stream(agg, chunk_size, num_chunks, rate))


# ---------------------------------------------------------------------------
# Standing-query registry.
# ---------------------------------------------------------------------------

def test_registry_matches_direct_queries(key):
    """The shared-pass evaluation must agree with calling each query
    helper directly on the same window."""
    from repro.core import query as q
    st = oasrs.init(3, 64, SPEC, key)
    agg = StreamAggregator(GaussianSource(), seed=1)
    c = agg.interval_chunk(0, 4096)
    st = oasrs.update_chunk(st, c.stratum_ids, c.values)
    w = win.init(2, 3, 64, SPEC, jax.random.fold_in(key, 1))
    w = win.slide(w, st)

    registry = _registry()
    kk = jax.random.fold_in(key, 7)
    out = registry.evaluate(w, kk)

    direct_sum = win.query_sum(w)
    direct_mean = win.query_mean(w)
    np.testing.assert_allclose(out["total"].value, direct_sum.value)
    np.testing.assert_allclose(out["total"].variance, direct_sum.variance)
    np.testing.assert_allclose(out["avg"].value, direct_mean.value)
    edges = jnp.asarray((0.0, 100.0, 5000.0, 2e4), jnp.float32)
    direct_hist = win.query_histogram(w, edges)
    np.testing.assert_allclose(out["hist"].value, direct_hist.value)
    direct_hh = win.query_heavy_hitters(w, 4)
    np.testing.assert_array_equal(np.asarray(out["top"].keys),
                                  np.asarray(direct_hh.keys))


def test_registry_validation():
    registry = QueryRegistry().register("a", "sum")
    with pytest.raises(ValueError, match="already registered"):
        registry.register("a", "mean")
    with pytest.raises(ValueError, match="unknown query kind"):
        registry.register("b", "median")
    with pytest.raises(ValueError, match="needs predicate"):
        registry.register("c", "count")
    with pytest.raises(ValueError, match="needs edges"):
        registry.register("d", "histogram")
    with pytest.raises(ValueError, match="needs qs"):
        registry.register("e", "quantile")


def test_registry_frozen_once_executor_built(key):
    """register() after an executor traced the registry must raise —
    cached window steps would otherwise serve stale query sets on some
    emissions and fresh ones on others."""
    reg = QueryRegistry().register("total", "sum")
    BatchedExecutor(_cfg(), reg, key)
    with pytest.raises(ValueError, match="frozen"):
        reg.register("late", "mean")


def test_registry_results_are_jit_stable(key):
    """evaluate() is pure jnp: jitted and eager paths agree."""
    w = win.init(2, 3, 32, SPEC, key)
    st = oasrs.init(3, 32, SPEC, jax.random.fold_in(key, 1))
    agg = StreamAggregator(GaussianSource(), seed=2)
    c = agg.interval_chunk(0, 1024)
    w = win.slide(w, oasrs.update_chunk(st, c.stratum_ids, c.values))
    registry = _registry()
    kk = jax.random.fold_in(key, 9)
    eager = registry.evaluate(w, kk)
    jitted = jax.jit(lambda ww, k: registry.evaluate(ww, k))(w, kk)
    for name in ("total", "avg", "p", "nuniq"):
        np.testing.assert_allclose(np.asarray(eager[name].value),
                                   np.asarray(jitted[name].value),
                                   rtol=1e-6)


# ---------------------------------------------------------------------------
# Watermarks.
# ---------------------------------------------------------------------------

def test_watermark_in_order_stream_never_drops():
    wm = wmk.init()
    open_iv = jnp.zeros((), jnp.int32)
    for e in range(6):
        times = jnp.float32(e) + jnp.linspace(0.0, 0.99, 64)
        r = wmk.route_chunk(wm, open_iv, times, jnp.ones((64,), bool),
                            span=1.0, allowed_lateness=0.0,
                            num_intervals=4)
        wm, open_iv = r.wm, r.open_interval
    assert int(wm.dropped) == 0 and int(wm.late) == 0
    assert int(wm.on_time) == 6 * 64
    assert int(open_iv) == 5


def test_watermark_routing_and_accounting():
    """Crafted arrivals: on-time, late-within-window, below-watermark,
    and evicted-interval items are each counted exactly once."""
    wm = wmk.init()
    open_iv = jnp.zeros((), jnp.int32)
    # Chunk 1: frontier to t=5.9 (interval 5). Window K=4 → live 2..5.
    t1 = jnp.asarray([5.1, 5.5, 5.9], jnp.float32)
    r1 = wmk.route_chunk(wm, open_iv, t1, jnp.ones((3,), bool),
                         span=1.0, allowed_lateness=2.0, num_intervals=4)
    assert int(r1.open_interval) == 5
    assert int(r1.wm.on_time) == 3
    # Chunk 2 (watermark now 5.9-2.0=3.9): 4.5 → late but accepted into
    # interval 4; 3.0 → below watermark, dropped; 1.5 → evicted interval
    # AND below watermark, dropped; 5.95 → on time.
    t2 = jnp.asarray([4.5, 3.0, 1.5, 5.95], jnp.float32)
    r2 = wmk.route_chunk(r1.wm, r1.open_interval, t2,
                         jnp.ones((4,), bool), span=1.0,
                         allowed_lateness=2.0, num_intervals=4)
    assert int(r2.wm.late) == 1
    assert int(r2.wm.dropped) == 2
    assert int(r2.wm.on_time) == 3 + 1
    np.testing.assert_array_equal(
        np.asarray(r2.accept), [True, False, False, True])
    np.testing.assert_array_equal(np.asarray(r2.target_interval),
                                  [4, 3, 1, 5])


def test_watermark_evicted_but_in_lateness_drops():
    """An item above the watermark whose interval already left the ring
    still drops (counted once, in `dropped`)."""
    wm = wmk.init()
    open_iv = jnp.zeros((), jnp.int32)
    r1 = wmk.route_chunk(wm, open_iv, jnp.asarray([9.5], jnp.float32),
                         jnp.ones((1,), bool), span=1.0,
                         allowed_lateness=6.0, num_intervals=4)
    # watermark = 3.5; interval 4 is above it but the ring holds 6..9.
    r2 = wmk.route_chunk(r1.wm, r1.open_interval,
                         jnp.asarray([4.5], jnp.float32),
                         jnp.ones((1,), bool), span=1.0,
                         allowed_lateness=6.0, num_intervals=4)
    assert int(r2.wm.dropped) == 1 and not bool(r2.accept[0])


def test_ingest_routes_late_items_to_correct_interval(key):
    """A late item must land in its OWN event interval's reservoir, not
    the newest one."""
    cfg = _cfg(capacity=8, num_intervals=4, interval_span=1.0,
               allowed_lateness=3.0)
    state = init_state(cfg, key)
    # Open intervals 0..3 with one marker item each (values 10·interval).
    for e in range(4):
        c = records.TimestampedChunk(
            values=jnp.asarray([10.0 * e], jnp.float32),
            stratum_ids=jnp.zeros((1,), jnp.int32),
            times=jnp.asarray([e + 0.5], jnp.float32),
            mask=jnp.ones((1,), bool))
        state = _ingest_chunk(cfg, state, c)
    # A late arrival for interval 1 (t=1.2 ≥ watermark 3.5-3.0).
    late = records.TimestampedChunk(
        values=jnp.asarray([999.0], jnp.float32),
        stratum_ids=jnp.zeros((1,), jnp.int32),
        times=jnp.asarray([1.2], jnp.float32),
        mask=jnp.ones((1,), bool))
    state = _ingest_chunk(cfg, state, late)
    assert int(state.wm.late) == 1 and int(state.wm.dropped) == 0
    slot_of_1 = 1 % cfg.num_intervals
    vals = np.asarray(state.window.intervals.values[slot_of_1, 0])
    cnt = int(state.window.intervals.counts[slot_of_1, 0])
    assert cnt == 2                      # marker + late arrival
    assert set(vals[:2]) == {10.0, 999.0}


def test_ingest_slot_reassignment_evicts_old_interval(key):
    """When interval K+j opens, slot j is reset: the old interval's items
    no longer contribute to queries."""
    cfg = _cfg(capacity=8, num_intervals=2, interval_span=1.0,
               allowed_lateness=0.0)
    state = init_state(cfg, key)

    def one_item(t, v):
        return records.TimestampedChunk(
            values=jnp.asarray([v], jnp.float32),
            stratum_ids=jnp.zeros((1,), jnp.int32),
            times=jnp.asarray([t], jnp.float32),
            mask=jnp.ones((1,), bool))

    state = _ingest_chunk(cfg, state, one_item(0.5, 100.0))  # interval 0
    state = _ingest_chunk(cfg, state, one_item(1.5, 200.0))  # interval 1
    state = _ingest_chunk(cfg, state, one_item(2.5, 300.0))  # evicts 0
    est = win.query_sum(state.window)
    assert float(est.value) == 500.0     # 200 + 300; 100 evicted
    np.testing.assert_array_equal(np.asarray(state.slot_interval), [2, 1])


# ---------------------------------------------------------------------------
# Controller.
# ---------------------------------------------------------------------------

def _stats(counts, taken, s):
    counts = jnp.asarray(counts, jnp.int32)
    taken = jnp.asarray(taken, jnp.int32)
    mean = jnp.asarray([10.0, 1000.0, 10000.0], jnp.float32)
    y = taken.astype(jnp.float32)
    return err.StratumStats(counts=counts, taken=taken, sums=y * mean,
                            sumsqs=y * (mean * mean + jnp.asarray(s) ** 2))


def test_controller_accuracy_feedback_grows_capacity():
    cfg = ControllerConfig(
        budget=adaptive.accuracy_budget(0.1, max_per_stratum=2048))
    st = ctl.init(jnp.full((3,), 16, jnp.int32))
    stats = _stats([50_000] * 3, [16] * 3, [5.0, 50.0, 500.0])
    realized = err.Estimate(value=jnp.float32(3700.0),
                            variance=jnp.float32(25.0))   # 2σ = 10 ≫ 0.1
    st2 = ctl.update(st, cfg, stats, realized, jnp.float32(0.001))
    assert int(jnp.max(st2.capacity)) > 16
    assert int(jnp.max(st2.capacity)) <= 2048


def test_controller_backpressure_sheds_capacity():
    cfg = ControllerConfig(budget=None, latency_budget_s=0.01)
    st = ctl.init(jnp.full((3,), 512, jnp.int32))
    stats = _stats([1000] * 3, [100] * 3, [5.0, 50.0, 500.0])
    realized = err.Estimate(value=jnp.float32(0.0),
                            variance=jnp.float32(0.0))
    st2 = ctl.update(st, cfg, stats, realized, jnp.float32(0.04))
    assert float(st2.pressure) == pytest.approx(4.0)
    assert int(st2.capacity[0]) == 128            # 512 / pressure
    # Relief is clamped: absurd pressure can't shed below min or 8×.
    st3 = ctl.update(st, cfg, stats, realized, jnp.float32(100.0))
    assert int(st3.capacity[0]) == 64             # 512 × 0.125 floor
    assert int(jnp.min(st3.capacity)) >= cfg.min_per_stratum
    # No ratchet: once latency recovers, capacity returns to baseline.
    st4 = st2
    for _ in range(6):
        st4 = ctl.update(st4, cfg, stats, realized, jnp.float32(0.001))
    assert int(st4.capacity[0]) == 512


def test_controller_disabled_keeps_capacity():
    cfg = ControllerConfig()
    st = ctl.init(jnp.full((3,), 64, jnp.int32))
    stats = _stats([1000] * 3, [64] * 3, [1.0, 1.0, 1.0])
    st2 = ctl.update(st, cfg, stats,
                     err.Estimate(value=jnp.float32(0.0),
                                  variance=jnp.float32(1e9)),
                     jnp.float32(123.0))
    np.testing.assert_array_equal(np.asarray(st2.capacity),
                                  np.asarray(st.capacity))


def test_next_batch_chunks_quantized():
    assert ctl.next_batch_chunks(4, pressure=2.0, max_batch_chunks=32) == 8
    assert ctl.next_batch_chunks(32, pressure=2.0, max_batch_chunks=32) == 32
    assert ctl.next_batch_chunks(8, pressure=0.2, max_batch_chunks=32) == 4
    assert ctl.next_batch_chunks(1, pressure=0.2, max_batch_chunks=32) == 1
    assert ctl.next_batch_chunks(8, pressure=0.8, max_batch_chunks=32) == 8
    # Doubling never exceeds a non-power-of-two maximum.
    assert ctl.next_batch_chunks(4, pressure=2.0, max_batch_chunks=6) == 6


def test_next_batch_chunks_per_window_pressure():
    """Watermark mode's per-window pressure: >1 interval close per
    micro-batch means the batch barrier paces emissions — the batch
    halves even when throughput pressure says grow; one (or zero)
    closes per batch leaves the throughput logic in charge."""
    assert ctl.next_batch_chunks(8, pressure=2.0, max_batch_chunks=32,
                                 closes_per_batch=2) == 4
    assert ctl.next_batch_chunks(8, pressure=0.8, max_batch_chunks=32,
                                 closes_per_batch=3) == 4
    assert ctl.next_batch_chunks(1, pressure=0.8, max_batch_chunks=32,
                                 closes_per_batch=4) == 1   # floor
    assert ctl.next_batch_chunks(4, pressure=2.0, max_batch_chunks=32,
                                 closes_per_batch=1) == 8
    assert ctl.next_batch_chunks(4, pressure=0.8, max_batch_chunks=32,
                                 closes_per_batch=0) == 4


# ---------------------------------------------------------------------------
# Executors end-to-end.
# ---------------------------------------------------------------------------

def test_batched_executor_estimates_within_bounds(key):
    cfg = _cfg(capacity=256)
    chunks = _chunks(num_chunks=16, chunk_size=512)
    ex = BatchedExecutor(cfg, _registry(), key)
    emissions = ex.run(chunks)
    assert len(emissions) == 4
    em = emissions[-1]
    exact = sum(float(jnp.sum(c.values)) for c in chunks)  # all 4 live
    est = em.results["total"]
    bound = 3.0 * math.sqrt(float(est.variance)) + 1e-3
    assert abs(float(est.value) - exact) < bound
    assert em.on_time == 16 * 512 and em.dropped == 0 and em.late == 0
    assert em.items == 4 * 512 and em.latency_s > 0.0


def test_pipelined_executor_continuous_emissions(key):
    cfg = _cfg(capacity=256, emit_every=2)
    chunks = _chunks(num_chunks=16, chunk_size=512)
    ex = PipelinedExecutor(cfg, _registry(), key)
    emissions = ex.run(chunks)
    assert len(emissions) == 8           # every 2 chunks — no batch barrier
    # Windowed answers track the moving window: compare each emission
    # against the exact sum of the intervals live at that point.
    em = emissions[-1]
    exact = sum(float(jnp.sum(c.values)) for c in chunks)
    est = em.results["total"]
    assert abs(float(est.value) - exact) < \
        3.0 * math.sqrt(float(est.variance)) + 1e-3


def test_pipelined_hot_loop_no_host_sync(key):
    """The per-chunk step must compile ONCE and contain no host
    callbacks or collectives — the Flink-mode hot-path contract."""
    cfg = _cfg(capacity=64, emit_every=10_000)   # no emission mid-run
    chunks = _chunks(num_chunks=12, chunk_size=256)
    ex = PipelinedExecutor(cfg, _registry(), key)
    for c in chunks:
        ex.push(c)
    assert ex.trace_count == 1, \
        f"pipelined step retraced {ex.trace_count} times"
    jaxpr = str(jax.make_jaxpr(
        lambda st, ch: _ingest_chunk(cfg, st, ch))(ex.state, chunks[0]))
    for prim in ("callback", "psum", "all_gather", "all_reduce",
                 "infeed", "outfeed"):
        assert prim not in jaxpr, f"{prim} in pipelined hot loop!"


def test_executor_requires_queries_and_validates_accuracy_query(key):
    with pytest.raises(ValueError, match="at least one"):
        BatchedExecutor(_cfg(), QueryRegistry(), key)
    with pytest.raises(ValueError, match="not registered"):
        BatchedExecutor(_cfg(accuracy_query="nope"),
                        QueryRegistry().register("total", "sum"), key)
    # The feedback signal must be a scalar linear estimate: a quantile
    # (vector value) or heavy-hitters (no .variance) query would explode
    # inside the first jitted emission instead of at construction.
    with pytest.raises(ValueError, match="sum/mean/count"):
        BatchedExecutor(
            _cfg(accuracy_query="p"),
            QueryRegistry().register("p", "quantile", qs=(0.5, 0.9)), key)


def test_controller_growth_never_exceeds_reservoir_allocation(key):
    """Accuracy feedback proposing capacity > N_max must not corrupt the
    slot buffer: N_max is sized for the budget ceiling and adopted
    capacities are clamped to it."""
    cfg = _cfg(
        capacity=16, batch_chunks=4, accuracy_query="avg",
        controller=ControllerConfig(
            budget=adaptive.accuracy_budget(0.001, max_per_stratum=512)))
    st = init_state(cfg, key)
    leaf = jax.tree_util.tree_leaves(st.window.intervals.values)[0]
    assert leaf.shape[2] == 512           # N_max covers the budget ceiling
    chunks = _chunks(num_chunks=16, chunk_size=512)
    reg = _registry()
    eb = BatchedExecutor(cfg, reg, key).run(chunks)
    ex = BatchedExecutor(cfg, reg, key)
    ex.run(chunks)
    n_max = 512
    assert int(jnp.max(ex.state.window.intervals.capacity)) <= n_max
    # …and the two modes still agree exactly under active adaptation is
    # NOT required (latency EMAs differ), but estimates must stay sane.
    est = eb[-1].results["total"]
    exact = sum(float(jnp.sum(c.values)) for c in chunks)
    assert abs(float(est.value) - exact) / exact < 0.05


def test_batched_backpressure_resizes_microbatch(key):
    """With an impossible latency budget the pressure signal must grow
    the micro-batch (throughput over latency), capped at the max."""
    cfg = _cfg(capacity=64, batch_chunks=2, max_batch_chunks=8,
               controller=ControllerConfig(latency_budget_s=1e-9))
    ex = BatchedExecutor(cfg, _registry(), key)
    ex.run(_chunks(num_chunks=24, chunk_size=256))
    assert ex.batch_chunks == 8


def test_adaptive_capacity_reaches_new_intervals(key):
    """Accuracy-budget feedback must change the capacity newly opened
    intervals are created with."""
    cfg = _cfg(
        capacity=16, batch_chunks=4,
        accuracy_query="avg",
        controller=ControllerConfig(
            budget=adaptive.accuracy_budget(0.05, max_per_stratum=512)))
    chunks = _chunks(num_chunks=16, chunk_size=512)
    ex = BatchedExecutor(cfg, _registry(), key)
    emissions = ex.run(chunks)
    cap_last = np.asarray(emissions[-1].capacity)
    assert int(cap_last.max()) > 16      # grew past the initial capacity
    # ... and the realized interval capacities follow the controller.
    assert int(jnp.max(ex.state.window.intervals.capacity)) > 16


# ---------------------------------------------------------------------------
# Sharded runtime (distributed wiring).
# ---------------------------------------------------------------------------

def _sharded_chunks(num_chunks=8, per_shard=256, shards=4, seed=3):
    agg = StreamAggregator(GaussianSource(), seed=seed)
    return [stamp_sharded(agg.sharded_interval(e, shards, per_shard),
                          e * 0.5, per_shard / 0.5)
            for e in range(num_chunks)]


def test_sharded_runtime_merges_shards(key):
    cfg = _cfg(capacity=256, num_shards=4, batch_chunks=2, emit_every=2)
    chunks = _sharded_chunks()
    ex = BatchedExecutor(cfg, _registry(), key)
    emissions = ex.run(chunks)
    exact = sum(float(jnp.sum(c.values)) for c in chunks)  # all live
    est = emissions[-1].results["total"]
    assert abs(float(est.value) - exact) < \
        3.0 * math.sqrt(float(est.variance)) + 1e-3
    assert emissions[-1].on_time == 8 * 4 * 256
    assert emissions[-1].items == 2 * 4 * 256     # last batch, all shards
    # Global capacity reported is the Σ over shards of N_i / w.
    assert int(emissions[-1].capacity[0]) == 4 * (256 // 4)


def test_sharded_modes_agree(key):
    cfg = _cfg(capacity=256, num_shards=4, batch_chunks=2, emit_every=2)
    chunks = _sharded_chunks()
    b = BatchedExecutor(cfg, _registry(), key).run(chunks)
    p = PipelinedExecutor(cfg, _registry(), key).run(chunks)
    np.testing.assert_array_equal(
        np.asarray(b[-1].results["total"].value),
        np.asarray(p[-1].results["total"].value))


def test_sharded_ingest_has_no_collectives(key):
    """The sharded per-chunk step is shard_map-shaped: its jaxpr must
    stay collective-free (paper §3.2 'no synchronization')."""
    cfg = _cfg(capacity=64, num_shards=2)
    state = init_state(cfg, key)
    chunk = _sharded_chunks(num_chunks=1, per_shard=64, shards=2)[0]
    core = jax.vmap(lambda st, ch: _ingest_chunk(cfg, st, ch),
                    in_axes=(0, 0))
    jaxpr = str(jax.make_jaxpr(core)(state, chunk))
    for prim in ("psum", "all_gather", "all_reduce", "ppermute",
                 "all_to_all"):
        assert prim not in jaxpr, f"collective {prim} in sharded ingest!"


def test_sharded_stats_merge_matches_global_psum(key):
    """The executor's Eq. 5 shard merge equals the single-psum merge in
    core/distributed.py run under shard_map."""
    from jax.sharding import PartitionSpec as P
    from repro.runtime.executor import _merged_view

    cfg = _cfg(capacity=128, num_shards=1)
    chunks = _chunks(num_chunks=4, chunk_size=256)
    ex = BatchedExecutor(cfg, _registry(), key)
    ex.run(chunks)
    _, stats, _ = _merged_view(cfg, ex.state)
    local = err.estimate_sum(stats)

    mesh = jax.make_mesh((1,), ("data",))
    fn = jax.shard_map(
        lambda s: jnp.stack(
            [dist.global_sum(s, "data").value,
             dist.global_sum(s, "data").variance]),
        mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(), stats),), out_specs=P())
    out = fn(stats)
    np.testing.assert_allclose(float(out[0]), float(local.value), rtol=1e-6)
    np.testing.assert_allclose(float(out[1]), float(local.variance),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# Records.
# ---------------------------------------------------------------------------

def test_stamp_in_order_and_deterministic():
    agg = StreamAggregator(GaussianSource(), seed=5)
    a = stamp(agg.interval_chunk(0, 64), 2.0, 64.0)
    assert float(a.times[0]) == 2.0
    assert float(a.times[-1]) < 3.0
    assert np.all(np.diff(np.asarray(a.times)) > 0)


def test_perturb_event_times_bounded(key):
    agg = StreamAggregator(GaussianSource(), seed=5)
    chunks = list(timestamped_stream(agg, 128, 4, 128.0))
    shuffled = records.perturb_event_times(chunks, key,
                                           max_displacement=0.25)
    for c, s in zip(chunks, shuffled):
        d = np.asarray(c.times) - np.asarray(s.times)
        assert np.all(d >= -1e-6) and np.all(d <= 0.25 + 1e-6)


def test_perturb_event_times_sharded(key):
    """perturb must compose with stamp_sharded ([W, M] time leaves)."""
    agg = StreamAggregator(GaussianSource(), seed=5)
    chunks = [stamp_sharded(agg.sharded_interval(0, 4, 16), 0.0, 16.0)]
    out = records.perturb_event_times(chunks, key, max_displacement=0.25)
    assert out[0].times.shape == (4, 16)
    d = np.asarray(chunks[0].times) - np.asarray(out[0].times)
    assert np.all(d >= -1e-6) and np.all(d <= 0.25 + 1e-6)


def test_executor_reset_reproduces_fresh_run(key):
    """reset(key) must restart the stream exactly (warm-then-time
    benchmarking relies on it) without recompiling the hot step."""
    cfg = _cfg(capacity=64, emit_every=4)
    chunks = _chunks(num_chunks=8, chunk_size=256)
    ex = PipelinedExecutor(cfg, _registry(), jax.random.fold_in(key, 1))
    ex.run(chunks[:4])                   # warm on a prefix
    ex.reset(key)
    warm_emissions = ex.run(chunks)
    assert ex.trace_count == 1
    fresh = PipelinedExecutor(cfg, _registry(), key).run(chunks)
    np.testing.assert_array_equal(
        np.asarray(warm_emissions[-1].results["total"].value),
        np.asarray(fresh[-1].results["total"].value))
    assert warm_emissions[-1].dropped == fresh[-1].dropped
