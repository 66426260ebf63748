"""Watermark-driven emission + session/per-key windows vs the
randomized event-time oracle (``tests/harness_event_time.py``).

The headline sweeps drive BOTH executor modes over ≥50 randomized
disordered streams each and assert, against the pure-numpy oracle:

* **when** — every interval's answers are emitted exactly once, in
  close order, at the exact arrival (pipelined) / containing flush
  (batched) whose frontier advance closed it;
* **what** — the emitted per-interval answers equal the oracle's
  accepted-item ground truth (capacities are sized so the reservoirs
  take everything — full-take stratified estimates are exact, so the
  comparison is sharp, not statistical);
* **accounting** — on-time/late/dropped match the oracle exactly.

Around the sweeps: session-assignment property tests against the
session oracle, an end-to-end sessionized stream, the hot-loop
sync-free contract under watermark emission, and the named refusals
(unclosable config, eviction-before-close, window-kind validation).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from harness_event_time import (expected_fire_index, oracle_run,
                                random_stream, run_tracking_emissions,
                                session_mask_oracle)
from repro.core import window as win
from repro.runtime import (BatchedExecutor, PipelinedExecutor,
                           QueryRegistry, RuntimeConfig, records,
                           silence_key)
from repro.runtime.executor import _ingest_chunk
from repro.stream import GaussianSource, ReplayableStream, StreamAggregator

MODES = (BatchedExecutor, PipelinedExecutor)
S = 3
CHUNK = 48
MAX_CHUNKS = 12
SPAN, LATENESS, K = 1.0, 0.3, 4


def _registry():
    return (QueryRegistry()
            .register("total", "sum")
            .register("cnt", "count", predicate=lambda x: x > -1.0)
            .register("key_sum", "sum", window="per_key")
            .register("key_cnt", "count", window="per_key",
                      predicate=lambda x: x > -1.0))


def _cfg(**kw):
    base = dict(num_strata=S, capacity=CHUNK * MAX_CHUNKS,
                num_intervals=K, interval_span=SPAN,
                allowed_lateness=LATENESS, batch_chunks=3, emit_every=3,
                emission="watermark")
    base.update(kw)
    return RuntimeConfig(**base)


# ---------------------------------------------------------------------------
# The randomized oracle sweep (the PR's acceptance property).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("make", MODES, ids=lambda m: m.mode)
def test_watermark_emission_matches_oracle_sweep(make, key):
    """≥50 randomized disordered streams per mode: emission schedule,
    per-interval answers and watermark accounting all equal the oracle."""
    cfg = _cfg()
    ex = make(cfg, _registry(), key)    # ONE warm executor for the sweep
    for trial in range(50):
        rng = np.random.default_rng(9000 + trial)
        chunks = random_stream(rng, S, chunk_size=CHUNK,
                               max_chunks=MAX_CHUNKS)
        oracle = oracle_run(chunks, SPAN, LATENESS, K, S)
        ex.reset(jax.random.fold_in(key, trial))
        emissions, fired_at = run_tracking_emissions(ex, chunks)

        # Exactly once, in close order.
        assert [em.interval for em in emissions] == \
            [iv for _, iv in oracle.closes], f"trial {trial}"
        assert [em.index for em in emissions] == list(range(len(emissions)))
        # ... at the right arrival / flush.
        expected = [expected_fire_index(e, ex.mode, cfg.batch_chunks,
                                        len(chunks))
                    for e, _ in oracle.closes]
        assert fired_at == expected, f"trial {trial}"

        # Emitted answers == the oracle's accepted-item ground truth
        # (full-take reservoirs: the stratified estimator is exact).
        for em in emissions:
            ivs = oracle.interval_sums.get(em.interval,
                                           np.zeros(S, np.float32))
            ivc = oracle.interval_counts.get(em.interval,
                                             np.zeros(S, np.int64))
            np.testing.assert_allclose(
                float(em.results["total"].value), ivs.sum(), rtol=1e-5,
                err_msg=f"trial {trial} interval {em.interval}")
            assert float(em.results["cnt"].value) == ivc.sum()
            np.testing.assert_allclose(
                np.asarray(em.results["key_sum"].value), ivs, rtol=1e-5,
                err_msg=f"trial {trial} interval {em.interval}")
            np.testing.assert_array_equal(
                np.asarray(em.results["key_cnt"].value),
                ivc.astype(np.float32))
            # Exact answers carry zero Eq. 6 variance (C_i == Y_i).
            assert float(jnp.max(em.results["total"].variance)) == 0.0

        # Full-stream accounting (read off the final device state —
        # watermark emissions stop at the last close, which may predate
        # the last chunk).
        _, _, on_time, late, dropped = ex._wm_totals(ex.state)
        assert (on_time, late, dropped) == \
            (oracle.on_time, oracle.late, oracle.dropped), f"trial {trial}"


def test_oracle_sweep_exercises_all_classes():
    """The generator must actually produce late AND dropped items over
    the sweep — otherwise the sweep's accounting assertions are
    vacuous."""
    tot = np.zeros(3, np.int64)
    for trial in range(50):
        rng = np.random.default_rng(9000 + trial)
        chunks = random_stream(rng, S, chunk_size=CHUNK,
                               max_chunks=MAX_CHUNKS)
        o = oracle_run(chunks, SPAN, LATENESS, K, S)
        tot += (o.on_time, o.late, o.dropped)
        assert len(o.closes) >= 1      # every stream closes something
    assert tot[0] > 0 and tot[1] > 0 and tot[2] > 0


# ---------------------------------------------------------------------------
# Session assignment: property test vs the oracle, then end to end.
# ---------------------------------------------------------------------------

@settings(max_examples=60, deadline=None)   # first example pays the JIT
@given(seed=st.integers(0, 10_000), k=st.integers(2, 8),
       s=st.integers(1, 4), gap=st.integers(1, 3))
def test_session_intervals_matches_oracle(seed, k, s, gap):
    rng = np.random.default_rng(seed)
    activity = rng.uniform(size=(k, s)) < 0.55
    base = int(rng.integers(0, 50))
    ids = base + rng.permutation(k).astype(np.int32)   # distinct, shuffled
    got = np.asarray(win.session_intervals(
        jnp.asarray(activity), jnp.asarray(ids, jnp.int32), gap))
    want = session_mask_oracle(activity, ids, gap)
    np.testing.assert_array_equal(got, want)


def test_session_query_end_to_end_matches_oracle(key):
    """A session-shaped stream (key 1 bursts 1s on / 1.5s off over an
    8×0.5s ring): the standing session query's per-key answer equals the
    oracle session's exact sums over the ring — and the gap timeout
    really cuts an earlier burst out of the current session."""
    n, chunk, k_ring, span = 16, 64, 8, 0.5
    rate = chunk / span                     # 1 chunk per interval
    stream = ReplayableStream(StreamAggregator(GaussianSource(), seed=17),
                              chunk_size=chunk, rate=rate,
                              key_gaps=((1, 1.0, 1.5),))
    chunks = stream.prefix(n)
    reg = (QueryRegistry()
           .register("total", "sum")
           .register("sess", "sum", window="session", session_gap=1.0))
    cfg = _cfg(capacity=n * chunk, emission="cadence", batch_chunks=4,
               num_intervals=k_ring, interval_span=span)
    ex = BatchedExecutor(cfg, reg, key)
    ex.run(chunks)

    oracle = oracle_run(chunks, span, LATENESS, k_ring, S)
    open_iv = int(np.max(np.asarray(ex.state.open_interval)))
    live = list(range(open_iv - k_ring + 1, open_iv + 1))
    slot_of = {iv: iv % k_ring for iv in live}
    activity = np.zeros((k_ring, S), bool)
    sums = np.zeros((k_ring, S), np.float32)
    slot_interval = np.zeros(k_ring, np.int64)
    for iv in live:
        slot_interval[slot_of[iv]] = iv
        if iv in oracle.interval_counts:
            activity[slot_of[iv]] = oracle.interval_counts[iv] > 0
            sums[slot_of[iv]] = oracle.interval_sums[iv]
    smask = session_mask_oracle(activity, slot_interval,
                                gap_intervals=2)     # ceil(1.0 / 0.5)
    expected = (sums * smask).sum(axis=0)

    got = np.asarray(ex.query()["sess"].value)
    np.testing.assert_allclose(got, expected, rtol=1e-5)
    # The gap actually matters: key 1's session must EXCLUDE some of its
    # live traffic (an active interval beyond the gap).
    all_live = (sums * activity).sum(axis=0)
    assert got[1] < all_live[1]
    assert smask.sum() < activity.sum()


def test_per_key_window_sums_match_oracle(key):
    """Per-key tumbling answers over the merged window equal per-key
    accepted sums over the live intervals (cadence emission)."""
    rng = np.random.default_rng(5)
    chunks = random_stream(rng, S, chunk_size=CHUNK, min_chunks=10,
                           max_chunks=10)
    cfg = _cfg(emission="cadence")
    ex = PipelinedExecutor(cfg, _registry(), key)
    ex.run(chunks)
    oracle = oracle_run(chunks, SPAN, LATENESS, K, S)
    open_iv = int(np.max(np.asarray(ex.state.open_interval)))
    expected = np.zeros(S, np.float64)
    for iv in range(open_iv - K + 1, open_iv + 1):
        expected += oracle.interval_sums.get(iv, np.zeros(S))
    np.testing.assert_allclose(np.asarray(ex.query()["key_sum"].value),
                               expected, rtol=1e-5)


# ---------------------------------------------------------------------------
# Hot-loop contract + named refusals.
# ---------------------------------------------------------------------------

def test_watermark_pipelined_hot_loop_sync_free(key):
    """Watermark emission must not change the hot-loop contract: the
    per-chunk step traces ONCE, the per-interval emit traces ONCE (for
    every interval and every reset), and the ingest jaxpr stays free of
    callbacks/collectives."""
    cfg = _cfg()
    rng = np.random.default_rng(77)
    chunks = random_stream(rng, S, chunk_size=CHUNK, min_chunks=10,
                           max_chunks=10)
    ex = PipelinedExecutor(cfg, _registry(), key)
    ex.run(chunks)
    ex.reset(jax.random.fold_in(key, 1))
    ex.run(chunks)
    assert len(ex.emissions) > 1
    assert ex.trace_count == 1, f"hot step retraced {ex.trace_count}x"
    assert ex.emit_trace_count == 1, \
        f"per-interval emit retraced {ex.emit_trace_count}x"
    jaxpr = str(jax.make_jaxpr(
        lambda st, ch: _ingest_chunk(cfg, st, ch))(ex.state, chunks[0]))
    for prim in ("callback", "psum", "all_gather", "all_reduce",
                 "infeed", "outfeed"):
        assert prim not in jaxpr, f"{prim} in watermark-mode hot loop!"


def test_watermark_config_must_let_intervals_close(key):
    """allowed_lateness >= (K-1)·span would evict every interval before
    its close — refused at construction with a named error."""
    reg = QueryRegistry().register("total", "sum")
    with pytest.raises(ValueError, match="watermark"):
        PipelinedExecutor(_cfg(allowed_lateness=3.0), reg, key)
    with pytest.raises(ValueError, match="emission mode"):
        PipelinedExecutor(_cfg(emission="punctuation"), reg, key)


def test_eviction_before_close_is_refused(key):
    """A single arrival unit jumping the frontier across a whole window
    closes intervals whose slots it already recycled — the runtime must
    refuse with a named error instead of emitting a recycled sample."""
    cfg = _cfg(allowed_lateness=2.0)
    ex = PipelinedExecutor(cfg, _registry(), key)

    def one(t):
        return records.TimestampedChunk(
            values=jnp.ones((4,), jnp.float32),
            stratum_ids=jnp.zeros((4,), jnp.int32),
            times=jnp.full((4,), t, jnp.float32),
            mask=jnp.ones((4,), bool))

    ex.push(one(0.5))
    with pytest.raises(RuntimeError, match="left the ring"):
        ex.push(one(50.0))


def test_window_kind_validation():
    reg = QueryRegistry()
    with pytest.raises(ValueError, match="unknown window"):
        reg.register("a", "sum", window="sliding")
    with pytest.raises(ValueError, match="session_gap"):
        reg.register("b", "sum", window="session")
    with pytest.raises(ValueError, match="session_gap must be > 0"):
        reg.register("c", "sum", window="session", session_gap=0.0)
    with pytest.raises(ValueError, match="merged window"):
        reg.register("d", "heavy_hitters", window="per_key")
    with pytest.raises(ValueError, match="merged window"):
        reg.register("e", "histogram", edges=(0.0, 1.0),
                     window="session", session_gap=1.0)
    # accuracy feedback needs a scalar — per-key vectors are refused.
    reg2 = (QueryRegistry().register("m", "mean")
            .register("km", "mean", window="per_key"))
    with pytest.raises(ValueError, match="SCALAR"):
        PipelinedExecutor(_cfg(accuracy_query="km", emission="cadence"),
                          reg2, jax.random.PRNGKey(0))


def test_session_grouped_quantile_smoke(key):
    """Per-key session quantiles (vmapped stratified bootstrap) run and
    bound the exact per-key medians for a full-take stream."""
    rng = np.random.default_rng(3)
    chunks = random_stream(rng, S, chunk_size=CHUNK, min_chunks=8,
                           max_chunks=8)
    reg = (QueryRegistry()
           .register("total", "sum")
           .register("kq", "quantile", qs=(0.5,), num_replicates=4,
                     window="per_key")
           .register("sq", "quantile", qs=(0.5,), num_replicates=4,
                     window="session", session_gap=2.0))
    ex = PipelinedExecutor(_cfg(emission="cadence"), reg, key)
    ex.run(chunks)
    out = ex.query()
    assert np.asarray(out["kq"].value).shape == (S, 1)
    assert np.asarray(out["sq"].value).shape == (S, 1)
    assert np.all(np.isfinite(np.asarray(out["kq"].value)))
