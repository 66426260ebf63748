"""One-shot ingest kernel tests (``RuntimeConfig.ingest="onekernel"``).

The tentpole contract: ONE Pallas call performs the whole accepted-item
path — watermark routing, ring-slot reset, (slot, stratum) cell
assignment, counter bump, replacement draw, conditional ring write and
the obs counter fold — and is BITWISE identical to (a) the numpy oracle
``kernels/ref.one_shot_ingest_ref`` at the kernel level, and (b) the
fused-jnp runtime path end to end: states chunk-for-chunk, emission
answers, Eq. 5–9 widths, obs counters, and crash/restore sweeps.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops as kops
from repro.kernels import ref
from repro.kernels import reservoir as rk
from repro.obs import metrics as obm
from repro.runtime import (BatchedExecutor, PipelinedExecutor,
                           QueryRegistry, RuntimeConfig, init_state,
                           perturb_event_times, timestamped_stream)
from repro.runtime.executor import _ingest_chunk
from repro.stream import GaussianSource, StreamAggregator
from harness_crash import sweep_crash_points


def _registry():
    return (QueryRegistry()
            .register("total", "sum")
            .register("avg", "mean")
            .register("hist", "histogram", edges=(0.0, 100.0, 5000.0, 2e4)))


def _cfg(**kw):
    base = dict(num_strata=3, capacity=64, num_intervals=4,
                interval_span=1.0, allowed_lateness=0.5,
                batch_chunks=4, emit_every=4)
    base.update(kw)
    return RuntimeConfig(**base)


def _chunks(num_chunks=12, chunk_size=256, seed=3, disorder=None, key=None):
    agg = StreamAggregator(GaussianSource(), seed=seed)
    rate = chunk_size * num_chunks / 4.0
    chunks = list(timestamped_stream(agg, chunk_size, num_chunks, rate))
    if disorder is not None:
        chunks = perturb_event_times(chunks, key, max_displacement=disorder)
    return chunks


def _assert_state_equal(a, b):
    for (pa, la), lb in zip(jax.tree_util.tree_flatten_with_path(a)[0],
                            jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(
            np.asarray(la), np.asarray(lb),
            err_msg=jax.tree_util.keystr(pa))


# ---------------------------------------------------------------------------
# Kernel vs the numpy oracle (edge geometry included).
# ---------------------------------------------------------------------------

def _oracle_case(K, S, N, M, block_m, mask_p=0.9, payload="f32", seed=1,
                 span=1.0, lateness=0.5):
    """Random pre-loaded ring + disordered chunk; kernel must equal the
    oracle bitwise on every output field."""
    rng = np.random.default_rng(seed)
    times = rng.uniform(0.0, 3.5, M).astype(np.float32)
    sid = rng.integers(0, S, M).astype(np.int32)
    if payload == "pytree":
        pay = {"val": rng.normal(size=M).astype(np.float32),
               "key": rng.integers(0, 1000, M).astype(np.int32)}
        values = {"val": rng.normal(size=(K, S, N)).astype(np.float32),
                  "key": rng.integers(0, 1000, (K, S, N)).astype(np.int32)}
    elif payload == "i32":
        pay = rng.integers(0, 9999, M).astype(np.int32)
        values = rng.integers(0, 9999, (K, S, N)).astype(np.int32)
    else:
        pay = rng.normal(size=M).astype(np.float32)
        values = rng.normal(size=(K, S, N)).astype(np.float32)
    mask = rng.random(M) < mask_p
    kw = dict(max_time=np.float32(0.7), open_interval=0, on_time=3,
              late=1, dropped=2, chunks=4, items=50,
              slot_interval=(-np.mod(-np.arange(K), K)).astype(np.int32),
              adopt=np.full((S,), min(5, N), np.int32),
              counts=rng.integers(0, 8, (K, S)).astype(np.int32),
              capacity=np.full((K, S), min(5, N), np.int32),
              values=values,
              counters=rng.integers(0, 3, (6, S)).astype(np.int32),
              span=span, allowed_lateness=lateness)
    ua = rng.random(M).astype(np.float32)
    us = rng.random(M).astype(np.float32)
    jkw = {k: (v if k in ("span", "allowed_lateness")
               else jax.tree.map(jnp.asarray, v)) for k, v in kw.items()}
    out = rk.one_shot_ingest(
        jnp.asarray(times), jnp.asarray(sid), jax.tree.map(jnp.asarray, pay),
        jnp.asarray(mask), jnp.asarray(ua), jnp.asarray(us),
        block_m=block_m, interpret=True, **jkw)
    r = ref.one_shot_ingest_ref(times, sid, pay, mask, ua, us, **kw)
    for name in ("counts", "capacity", "slot_interval", "max_time",
                 "open_interval", "on_time", "late", "dropped", "chunks",
                 "items", "counters"):
        np.testing.assert_array_equal(np.asarray(getattr(out, name)),
                                      np.asarray(r[name]), err_msg=name)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), out.values, r["values"])
    return out


@pytest.mark.parametrize("m,block_m", [
    (300, 128),       # chunk not a multiple of the item tile
    (50, 256),        # chunk smaller than one tile
    (256, 128),       # exact multiple
    pytest.param(1024, 64, marks=pytest.mark.slow),
])
def test_kernel_matches_oracle_tile_geometry(m, block_m):
    _oracle_case(4, 3, 8, m, block_m)


def test_kernel_matches_oracle_all_masked():
    """A fully late/dropped (all-items-masked-out) chunk still resets
    slots, bumps nothing, and carries the counters through."""
    out = _oracle_case(4, 3, 8, 128, 128, mask_p=0.0)
    assert int(out.items) == 50          # unchanged scalar totals (+0)


def test_kernel_matches_oracle_single_cell():
    """K·S == 1: the ring degenerates to one cell; the desired-occupant
    arithmetic and the counter slices must still hold."""
    _oracle_case(1, 1, 4, 77, 32)
    _oracle_case(1, 3, 4, 64, 64)        # single-slot ring, S > 1


@pytest.mark.parametrize("payload", ["i32", "pytree"])
def test_kernel_matches_oracle_payload_layouts(payload):
    """Int payloads and pytree payloads (heavy-hitter keys) ride the
    kernel: every leaf folds through the same accept/slot decisions."""
    _oracle_case(4, 3, 8, 200, 64, payload=payload)


@pytest.mark.slow
def test_kernel_matches_oracle_randomized_sweep():
    for seed in range(8):
        rng = np.random.default_rng(100 + seed)
        _oracle_case(int(rng.integers(1, 6)), int(rng.integers(1, 5)),
                     int(rng.integers(2, 10)), int(rng.integers(1, 400)),
                     int(rng.integers(1, 4)) * 64,
                     mask_p=float(rng.random()), seed=seed)


def test_kernel_payload_structure_validation(key):
    """Mismatched payload/values structure or non-scalar layouts must
    fail loudly, not mis-index the ring."""
    args = dict(max_time=jnp.float32(0.0), open_interval=jnp.int32(0),
                on_time=jnp.int32(0), late=jnp.int32(0),
                dropped=jnp.int32(0), chunks=jnp.int32(0),
                items=jnp.int32(0),
                slot_interval=jnp.zeros((2,), jnp.int32),
                adopt=jnp.full((2,), 4, jnp.int32),
                counts=jnp.zeros((2, 2), jnp.int32),
                capacity=jnp.full((2, 2), 4, jnp.int32),
                counters=jnp.zeros((6, 2), jnp.int32),
                span=1.0, allowed_lateness=0.5)
    m = jnp.zeros((8,))
    items = (m, jnp.zeros((8,), jnp.int32), m, jnp.ones((8,), bool), m, m)
    with pytest.raises(ValueError, match="structure"):
        rk.one_shot_ingest(items[0], items[1], {"a": m}, *items[3:],
                           values=jnp.zeros((2, 2, 4)), interpret=True,
                           **args)
    with pytest.raises(ValueError, match="scalar payload"):
        rk.one_shot_ingest(items[0], items[1], m, *items[3:],
                           values=jnp.zeros((2, 2, 4, 3)), interpret=True,
                           **args)


# ---------------------------------------------------------------------------
# Runtime: onekernel == fused, bitwise, chunk for chunk.
# ---------------------------------------------------------------------------

def test_onekernel_equals_fused_chunk_for_chunk(key):
    """Same uniforms from the ring's lead key, same routing arithmetic,
    same counter semantics — the whole RuntimeState (ring, watermark,
    obs counters) must agree bitwise after EVERY chunk, including late
    arrivals and slot evictions (the disorder exercises both)."""
    cfg_f = _cfg()
    cfg_o = _cfg(ingest="onekernel")
    chunks = _chunks(disorder=0.35, key=jax.random.fold_in(key, 1))
    sf = init_state(cfg_f, key)
    so = init_state(cfg_o, key)
    for c in chunks:
        sf = _ingest_chunk(cfg_f, sf, c)
        so = _ingest_chunk(cfg_o, so, c)
        _assert_state_equal(sf, so)
    assert int(sf.wm.late) > 0          # the sweep exercised late routing


def test_onekernel_dispatch_and_validation(key):
    st = init_state(_cfg(ingest="onekernel"), key)
    c = _chunks(num_chunks=1)[0]
    from repro.runtime.executor import _ingest_chunk_onekernel
    _assert_state_equal(_ingest_chunk(_cfg(ingest="onekernel"), st, c),
                        _ingest_chunk_onekernel(_cfg(), st, c))
    with pytest.raises(ValueError, match="onekernel"):
        _ingest_chunk(_cfg(ingest="nope"), st, c)


def test_onekernel_sharded_equals_fused(key):
    """The vmap-sharded core batches the Pallas call (interpret mode)
    without breaking the bitwise contract."""
    from repro.runtime import stamp_sharded
    cfg_f = _cfg(num_shards=2)
    cfg_o = _cfg(num_shards=2, ingest="onekernel")
    agg = StreamAggregator(GaussianSource(), seed=7)
    chunks = [stamp_sharded(agg.sharded_interval(e, 2, 128),
                            e * 0.5, 128 / 0.5) for e in range(6)]
    sf = init_state(cfg_f, key)
    so = init_state(cfg_o, key)
    core_f = jax.vmap(lambda st, ch: _ingest_chunk(cfg_f, st, ch))
    core_o = jax.vmap(lambda st, ch: _ingest_chunk(cfg_o, st, ch))
    for c in chunks:
        sf, so = core_f(sf, c), core_o(so, c)
    _assert_state_equal(sf, so)


def test_onekernel_executor_emissions_equal_fused(key):
    """End to end, both executor modes: answers AND Eq. 5–9 interval
    widths are bitwise those of the fused path."""
    chunks = _chunks(num_chunks=16, chunk_size=256)
    for mode in (BatchedExecutor, PipelinedExecutor):
        ef = mode(_cfg(), _registry(), key).run(chunks)
        eo = mode(_cfg(ingest="onekernel"), _registry(), key).run(chunks)
        assert len(ef) == len(eo) == 4
        for a, b in zip(ef, eo):
            for name in a.results:
                np.testing.assert_array_equal(
                    np.asarray(a.results[name].value),
                    np.asarray(b.results[name].value), err_msg=name)
                np.testing.assert_array_equal(
                    np.asarray(a.results[name].variance),
                    np.asarray(b.results[name].variance), err_msg=name)
            assert (a.on_time, a.late, a.dropped) == \
                (b.on_time, b.late, b.dropped)


def test_onekernel_obs_counters_equal_fused(key):
    """The counters folded INSIDE the kernel reproduce
    ``obs/metrics.ingest_update`` exactly (the ``tests/test_obs.py``
    oracle contract transfers)."""
    cfg_f, cfg_o = _cfg(), _cfg(ingest="onekernel")
    chunks = _chunks(disorder=0.3, key=jax.random.fold_in(key, 5))
    sf, so = init_state(cfg_f, key), init_state(cfg_o, key)
    for c in chunks:
        sf = _ingest_chunk(cfg_f, sf, c)
        so = _ingest_chunk(cfg_o, so, c)
    assert obm.counters(sf.metrics) .keys() == \
        obm.counters(so.metrics).keys()
    for name, a in obm.counters(sf.metrics).items():
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(obm.counters(so.metrics)[name]),
            err_msg=name)
    assert int(so.metrics.chunks) == len(chunks)
    assert int(jnp.sum(so.metrics.replaced)) > 0


def test_onekernel_watermark_emission_equal_fused(key):
    """Watermark-driven emission (event-time closes) on the onekernel
    path emits the same (interval, answer) sequence as fused."""
    chunks = _chunks(num_chunks=16, chunk_size=256)
    ef = PipelinedExecutor(_cfg(emission="watermark"), _registry(),
                           key).run(chunks)
    eo = PipelinedExecutor(_cfg(emission="watermark", ingest="onekernel"),
                           _registry(), key).run(chunks)
    assert [e.interval for e in ef] == [e.interval for e in eo]
    assert len(ef) > 0
    for a, b in zip(ef, eo):
        np.testing.assert_array_equal(
            np.asarray(a.results["total"].value),
            np.asarray(b.results["total"].value))


def test_onekernel_metrics_rows_donatable(key):
    """unstack_counters must hand the executors six independently
    donatable buffers — two steps in a row may not trip XLA's
    duplicate-donation check."""
    cfg = _cfg(ingest="onekernel", emit_every=10_000)
    ex = PipelinedExecutor(cfg, _registry(), key)
    for c in _chunks(num_chunks=4):
        ex.push(c)
    assert ex.trace_count == 1


def test_onekernel_hot_loop_stays_host_free(key):
    """No host callbacks or collectives may hide inside the kernel
    call's jaxpr."""
    cfg = _cfg(ingest="onekernel")
    state = init_state(cfg, key)
    c = _chunks(num_chunks=1)[0]
    jaxpr = str(jax.make_jaxpr(
        lambda st, ch: _ingest_chunk(cfg, st, ch))(state, c))
    for prim in ("callback", "psum", "all_gather", "all_reduce",
                 "infeed", "outfeed"):
        assert prim not in jaxpr, f"{prim} in onekernel hot loop!"


# ---------------------------------------------------------------------------
# Crash/restore: exactly-once survives the kernel path.
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_onekernel_crash_restore_sweep(key):
    """Kill-after-chunk-k for several k: recovery on the onekernel path
    must re-emit the uninterrupted run's answers bitwise (PR-3 harness,
    PR-6 counters and the kernel state all ride the same checkpoint)."""
    from repro.stream import ReplayableStream
    cfg = _cfg(ingest="onekernel", emit_every=2)
    n, chunk_size = 10, 128
    stream = ReplayableStream(
        StreamAggregator(GaussianSource(), seed=3),
        chunk_size=chunk_size, rate=chunk_size * n / 4.0, disorder=0.25)
    sweep_crash_points(
        make_victim=lambda: PipelinedExecutor(cfg, _registry(), key),
        make_recovery=lambda: PipelinedExecutor(
            cfg, _registry(), jax.random.PRNGKey(999)),
        stream=stream, num_chunks=n, crash_points=(1, 4, 7),
        every_chunks=2, key=key)


def test_onekernel_checkpoint_roundtrip(key):
    """Snapshot/restore mid-stream; the continuation equals the
    uninterrupted run's final emission."""
    chunks = _chunks(num_chunks=8, chunk_size=128)
    cfg = _cfg(ingest="onekernel", emit_every=2)
    ex = PipelinedExecutor(cfg, _registry(), key)
    for c in chunks[:4]:
        ex.push(c)
    payload = ex.snapshot()
    full = ex.run(chunks[4:])
    rec = PipelinedExecutor(cfg, _registry(), jax.random.fold_in(key, 9))
    rec.restore(payload)
    rec_emissions = rec.run(chunks[4:])
    np.testing.assert_array_equal(
        np.asarray(full[-1].results["total"].value),
        np.asarray(rec_emissions[-1].results["total"].value))


# ---------------------------------------------------------------------------
# ops-level plumbing (the dedup satellite).
# ---------------------------------------------------------------------------

def test_default_interpret_single_source(monkeypatch):
    """kernels/ops owns the one platform rule — interpret exactly on a
    CPU backend — and oasrs's default fold backend follows it: the
    compiled kernel on TPU, the jnp fold elsewhere."""
    from repro.core import oasrs
    for platform, interpret, backend in (("cpu", True, "jnp"),
                                         ("tpu", False, "pallas"),
                                         ("gpu", False, "jnp")):
        monkeypatch.setattr(jax, "default_backend", lambda: platform)
        assert kops.interpret_mode() is interpret
        assert oasrs.default_backend() == backend
    assert not hasattr(kops, "pallas_compile_enabled")
    assert not hasattr(rk, "default_interpret")   # hoisted out of reservoir
