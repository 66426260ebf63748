"""Program spans (``repro.obs.spans``) on the profiler trace.

A watermark-mode pipelined run under ``jax.profiler`` leaves one
``stream.push`` per push, holding one ``stream.dispatch`` and one
``stream.frontier``, and one ``stream.emit`` per emission, holding one
``stream.readback``.  The spans wrap host code only: the emissions are
bitwise those of an untraced run, the step traces once, and the compiled
programs keep the names the benchmark's trace reduction matches.
"""
import glob
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.obs import spans
from repro.runtime import PipelinedExecutor, QueryRegistry, RuntimeConfig
from repro.stream import GaussianSource, ReplayableStream, StreamAggregator

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _executor():
    cfg = RuntimeConfig(num_strata=3, capacity=16, num_intervals=4,
                        interval_span=1.0, allowed_lateness=0.4,
                        emission="watermark")
    reg = QueryRegistry().register("avg", "mean").register("total", "sum")
    return PipelinedExecutor(cfg, reg, jax.random.PRNGKey(0))


def _chunks():
    src = ReplayableStream(StreamAggregator(GaussianSource(), seed=5),
                           chunk_size=96, rate=384.0, disorder=0.3,
                           disorder_seed=2)
    return src.prefix(12)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A traced run: the executor, its chunks and the program spans of the
    trace as ``(name, start_ns, end_ns)``."""
    out = str(tmp_path_factory.mktemp("trace"))
    chunks = _chunks()
    ex = _executor()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        for c in chunks:
            ex.push(c)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                      recursive=True)
    found = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
             for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for ev in line.events
             if ev.name in spans.NAMES]
    return ex, chunks, found


def _inside(outer, found, name):
    _, s, e = outer
    return [sp for sp in found if sp[0] == name and s <= sp[1]
            and sp[2] <= e]


def _named(found, name):
    return [sp for sp in found if sp[0] == name]


def test_each_push_holds_one_dispatch_and_one_frontier(traced):
    _, chunks, found = traced
    pushes = _named(found, spans.PUSH)
    assert len(pushes) == len(chunks)
    for push in pushes:
        assert len(_inside(push, found, spans.DISPATCH)) == 1
        assert len(_inside(push, found, spans.FRONTIER)) == 1
    assert len(_named(found, spans.DISPATCH)) == len(chunks)
    assert len(_named(found, spans.FRONTIER)) == len(chunks)


def test_each_emission_holds_one_readback(traced):
    ex, _, found = traced
    emits = _named(found, spans.EMIT)
    assert len(ex.emissions) >= 2
    assert len(emits) == len(ex.emissions)
    for emit in emits:
        assert len(_inside(emit, found, spans.READBACK)) == 1
        assert len([p for p in _named(found, spans.PUSH)
                    if p[1] <= emit[1] and emit[2] <= p[2]]) == 1
    assert len(_named(found, spans.READBACK)) == len(ex.emissions)


def test_emissions_bitwise_equal_untraced(traced):
    ex, chunks, _ = traced
    plain = _executor()
    for c in chunks:
        plain.push(c)
    assert len(plain.emissions) == len(ex.emissions)
    for a, b in zip(ex.emissions, plain.emissions):
        assert (a.index, a.interval, a.watermark, a.open_interval,
                a.on_time, a.late, a.dropped, a.items) == \
            (b.index, b.interval, b.watermark, b.open_interval,
             b.on_time, b.late, b.dropped, b.items)
        assert np.array_equal(a.capacity, b.capacity)
        for x, y in zip(jax.tree.leaves(jax.device_get(a.results)),
                        jax.tree.leaves(jax.device_get(b.results))):
            assert np.array_equal(np.asarray(x), np.asarray(y))


def test_programs_unchanged(traced):
    ex, chunks, _ = traced
    assert ex.trace_count == 1
    assert ex.emit_trace_count == 1
    step = ex._step.lower(ex.state, chunks[0]).as_text()
    emit = ex._emit_interval_fn.lower(
        ex.state, jnp.int32(0), ex._emit_base_key,
        jnp.float32(0.0)).as_text()
    assert step.startswith("module @jit_core ")
    assert emit.startswith("module @jit_emit_iv ")


def test_names_are_the_benchmark_readers():
    spec = importlib.util.spec_from_file_location(
        "bench_metrics_spans", os.path.join(ROOT, "bench", "metrics",
                                            "_spans.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    # The mesh's placement span is read by its own reader
    # (bench/metrics/place_ms.mesh.py), not by the shared span nesting.
    assert bench.NAMES == tuple(n for n in spans.NAMES if n != spans.PLACE)
    assert (bench.PUSH, bench.DISPATCH, bench.FRONTIER, bench.EMIT,
            bench.READBACK) == (spans.PUSH, spans.DISPATCH, spans.FRONTIER,
                                spans.EMIT, spans.READBACK)
