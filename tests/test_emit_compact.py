"""The closed interval's emission reads only the interval's cells.

``_evaluate_interval`` takes slot ``j mod K``'s rows out of the merged
view (``[W·K·S, N] → [W·S, N]``) and the per-key quantile gives each key
only its own rows. These tests hold that compacted emission against the
masked full-ring evaluation it replaces — the same closed interval, with
the other cells zeroed — on single-shard, sharded-vmap and mesh states:
linear answers and their variances bitwise, quantile point values
bitwise, a recycled slot answering zero. A shape guard keeps the
bootstrap's gathers and sorts at the compacted size, and the ``run_meta``
event carries the rows each estimator reads.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import error as err
from repro.core import window as win
from repro.obs import EventLog, Telemetry
from repro.runtime import PipelinedExecutor, QueryRegistry, RuntimeConfig
from repro.runtime import executor as exm
from repro.stream import GaussianSource, StreamAggregator
from repro.stream.replay import ReplayableStream

S, K, N = 3, 4, 8
LINEAR = ("total", "avg", "cnt", "key_avg", "key_sum")
QUANTILES = ("p", "key_p")


def _registry():
    return (QueryRegistry()
            .register("total", "sum")
            .register("avg", "mean")
            .register("cnt", "count", predicate=lambda x: x > 0.0)
            .register("p", "quantile", qs=(0.5, 0.99), num_replicates=4)
            .register("key_avg", "mean", window="per_key")
            .register("key_sum", "sum", window="per_key")
            .register("key_p", "quantile", qs=(0.5, 0.95),
                      num_replicates=4, window="per_key"))


def _cfg(w, placement):
    return RuntimeConfig(num_strata=S, capacity=N * w, num_intervals=K,
                         interval_span=1.0, allowed_lateness=0.5,
                         num_shards=w, placement=placement,
                         emission="watermark")


def _stream(w):
    return ReplayableStream(
        aggregator=StreamAggregator(GaussianSource(), seed=11),
        chunk_size=32, rate=48.0, num_shards=w,
        disorder=0.3, disorder_seed=5)


def _state(cfg, key, chunks=9):
    """A live executor state after ``chunks`` pushes: several intervals
    in the ring, reservoirs sampling (arrivals above capacity)."""
    ex = PipelinedExecutor(cfg, _registry(), key)
    for c in _stream(cfg.num_shards).prefix(chunks):
        ex.push(c)
    return ex


def _masked(cfg, registry, state, interval, base_key):
    """The full-ring evaluation of one interval: every cell kept, the
    ones outside the interval's slot (or of a shard whose slot no longer
    holds it) zeroed."""
    w = cfg.num_shards
    view, stats, _ = exm._merged_view(cfg, state)
    ctx = exm._window_ctx(cfg, state, view, stats)
    ctx.activity = ctx.activity & (ctx.slot_interval <= interval)[:, None]
    slot = interval % K
    holds = state.slot_interval.reshape(w, K)[:, slot] == interval   # [W]
    mask = holds[:, None, None] & (jnp.arange(K) == slot)[None, :, None]
    iview = win.restrict_view(
        view, jnp.broadcast_to(mask, (w, K, S)).reshape(-1))
    istats = err.stratum_stats_from_sample(
        iview.values, iview.counts, iview.taken, iview.slot_mask(),
        fixed_order=w > 1)
    results = registry.evaluate_view(
        iview, istats, jax.random.fold_in(base_key, interval), ctx=ctx)
    # The slot's cells of the masked stats, in the compacted [W·S] order.
    cells = jax.tree.map(
        lambda x: x.reshape(w, K, S)[:, slot].reshape(-1), istats)
    return results, cells


def _compacted(ex):
    """The executor's closed-interval evaluation, jitted in its placement:
    ``fn(interval) -> (results, istats)``."""
    cfg, registry, axis = ex.cfg, ex.registry, ex._axis
    fn = jax.jit(ex._shard_wrap(
        lambda st, j, k: exm._evaluate_interval(cfg, registry, st, j, k,
                                                axis=axis),
        n_sharded=1, n_replicated=2, out_sharded=0, out_replicated=1))
    return lambda j: fn(ex.state, jnp.int32(j), ex._emit_base_key)


PLACEMENTS = [pytest.param(1, "vmap", id="single"),
              pytest.param(4, "vmap", id="sharded-vmap"),
              pytest.param(4, "mesh", id="mesh")]


@pytest.mark.parametrize("w,placement", PLACEMENTS)
def test_compacted_interval_matches_masked_ring(w, placement, key):
    ex = _state(_cfg(w, placement), key)
    host = jax.device_get(ex.state)
    oracle_cfg = _cfg(w, "vmap")
    masked_fn = jax.jit(lambda st, j, k: _masked(oracle_cfg, ex.registry,
                                                 st, j, k))
    compacted = _compacted(ex)
    live = sorted(set(np.asarray(host.slot_interval).reshape(-1).tolist()))
    live = [j for j in live if j >= 0]
    assert len(live) >= 3, live
    counts = np.asarray(host.window.intervals.counts)
    assert (counts > N).any(), "reservoirs must be sampling"
    for j in live:
        got, got_cells = compacted(j)
        want, want_cells = masked_fn(host, jnp.int32(j), ex._emit_base_key)
        # The same cells: per-cell counts, sample sizes and moments are
        # bitwise those of the masked ring.
        for a, b in zip(jax.tree_util.tree_leaves(got_cells),
                        jax.tree_util.tree_leaves(want_cells)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # The cross-cell sums add W·S terms instead of W·K·S (the rest
        # zeros), and XLA's fused reductions may order them otherwise:
        # f32 rounding of a 12- or 48-term sum, a few units in the last
        # place at most.
        for name in LINEAR:
            np.testing.assert_allclose(np.asarray(got[name].value),
                                       np.asarray(want[name].value),
                                       rtol=1e-6, atol=0,
                                       err_msg=f"{name} @ {j}")
            np.testing.assert_allclose(np.asarray(got[name].variance),
                                       np.asarray(want[name].variance),
                                       rtol=1e-6, atol=0,
                                       err_msg=f"{name} var @ {j}")
        for name in QUANTILES:
            np.testing.assert_array_equal(np.asarray(got[name].value),
                                          np.asarray(want[name].value),
                                          err_msg=f"{name} @ {j}")
        assert float(got["cnt"].value) > 0.0


@pytest.mark.parametrize("w,placement", PLACEMENTS)
def test_recycled_slot_answers_zero(w, placement, key):
    """Interval ``j - K`` shares slot ``j``'s cells but is gone: the
    compacted view keeps the rows yet weighs them zero."""
    ex = _state(_cfg(w, placement), key)
    newest = int(np.max(np.asarray(jax.device_get(ex.state.slot_interval))))
    got, _ = _compacted(ex)(newest - K)
    for name in ("total", "avg", "cnt", "key_avg", "key_sum"):
        np.testing.assert_array_equal(np.asarray(got[name].value), 0.0)
        np.testing.assert_array_equal(np.asarray(got[name].variance), 0.0)
    # No valid slot: every quantile falls through to the +inf stand-in.
    assert np.all(np.asarray(got["key_p"].value) > 1e38)


# ---------------------------------------------------------------------------
# Shape guard: the bootstrap's gathers and sorts stay at the kept rows.
# ---------------------------------------------------------------------------

def _walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub)


def _largest_gather_or_sort(jaxpr) -> int:
    sizes = [int(np.prod(v.aval.shape))
             for eqn in _walk(jaxpr)
             if eqn.primitive.name in ("gather", "sort")
             for v in list(eqn.invars) + list(eqn.outvars)
             if hasattr(v.aval, "shape")]
    return max(sizes)


R = 32


@pytest.mark.parametrize("shape", [
    pytest.param(dict(w=1, k=4, s=3, n=64, per_key=False), id="netflow"),
    pytest.param(dict(w=1, k=4, s=6, n=40, per_key=True), id="taxi"),
    pytest.param(dict(w=2, k=4, s=6, n=40, per_key=True),
                 id="taxi-sharded"),
])
def test_emit_iv_gathers_only_the_closed_interval(shape, key):
    """A netflow-like registry (merged p99) and a taxi-like one (per-key
    p95): the largest gather/sort operand of ``emit_iv`` has ``R·W·S·N``
    elements — ``R·W·N`` per key — and not the ring's ``R·W·K·S·N``."""
    w, k, s, n = shape["w"], shape["k"], shape["s"], shape["n"]
    reg = QueryRegistry().register("mean", "mean").register(
        "count", "count", predicate=lambda x: x > 0.0)
    if shape["per_key"]:
        reg.register("key_mean", "mean", window="per_key")
        reg.register("key_p95", "quantile", qs=(0.95,), window="per_key",
                     num_replicates=R)
    else:
        reg.register("sum", "sum")
        reg.register("p99", "quantile", qs=(0.99,), num_replicates=R)
    cfg = RuntimeConfig(num_strata=s, capacity=n * w, num_intervals=k,
                        num_shards=w, emission="watermark")
    ex = PipelinedExecutor(cfg, reg, key)
    jaxpr = jax.make_jaxpr(ex._emit_interval_fn)(
        ex.state, jnp.int32(0), ex._emit_base_key, jnp.float32(0.0))
    largest = _largest_gather_or_sort(jaxpr.jaxpr)
    assert largest == R * w * s * n, (largest, R * w * s * n)
    if shape["per_key"]:
        assert largest // s == R * w * n
    assert largest < R * w * k * s * n


def test_run_meta_carries_emit_cells(key):
    cfg = RuntimeConfig(num_strata=6, capacity=8, num_intervals=4,
                        num_shards=2, emission="watermark")
    reg = (QueryRegistry().register("mean", "mean")
           .register("key_mean", "mean", window="per_key")
           .register("key_p95", "quantile", qs=(0.95,), window="per_key")
           .register("sess_p", "quantile", qs=(0.5,), window="session",
                     session_gap=1.0))
    log = EventLog()
    ex = PipelinedExecutor(cfg, reg, key, telemetry=Telemetry(log=log))
    meta = [e for e in log.events if e["type"] == "run_meta"]
    assert len(meta) == 1
    ring, kept = 2 * 4 * 6, 2 * 6
    assert meta[0]["emit_cells"] == {
        "ring": ring,
        "kept": {"mean": kept, "key_mean": kept, "key_p95": kept // 6,
                 "sess_p": ring // 6}}
    assert ex.emit_trace_count == 0       # an abstract trace, not a compile
    cadence = PipelinedExecutor(
        RuntimeConfig(num_strata=6, capacity=8, num_intervals=4),
        QueryRegistry().register("mean", "mean"), key)
    assert cadence.emit_cells() == {"ring": 24, "kept": {"mean": 24}}
