"""The per-layer readers of the program's own spans (``stream.*``): their
numbers on a synthetic trace, ``None`` on a trace without program spans,
and the split of device idle time by the host span it fell in."""
import importlib.util
import os
import types

import pytest

import _bench_paths as bp
import _spans

_spec = importlib.util.spec_from_file_location(
    "bench_trace", os.path.join(bp.BENCH, "trace.py"))
trace = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(trace)


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(bp.BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _push(t, push_ms, dispatch_ms, frontier_ms, emits=()):
    """A benchmark push at ``t`` holding a program push; ``emits`` are
    ``(emit_ms, readback_ms)`` closes after the frontier test."""
    s = t + 1e-4
    d_end = s + 1e-4 + dispatch_ms * 1e-3
    f_end = d_end + frontier_ms * 1e-3
    out = [("stream.dispatch", s + 1e-4, d_end),
           ("PjitFunction(core)", s + 2e-4, d_end - 1e-4),
           ("stream.frontier", d_end, f_end)]
    at = f_end
    for emit_ms, readback_ms in emits:
        end = at + emit_ms * 1e-3
        out += [("stream.emit", at, end),
                ("stream.readback", end - readback_ms * 1e-3, end)]
        at = end
    end = max(at, s + push_ms * 1e-3)
    out.append(("stream.push", s, end))
    out.append(("bench.push", t, end + 1e-4))
    return out, end + 1e-4


def _spans_fixture():
    spans = [("stream.push", -0.02, -0.01), ("stream.emit", -0.019, -0.011),
             ("stream.readback", -0.012, -0.011)]
    t = 0.0
    for args in [(1.2, 1.0, 0.05), (1.4, 1.1, 0.1), (1.0, 0.9, 0.02),
                 (0, 1.0, 0.1, [(150.0, 8.0), (158.0, 6.0)])]:
        got, t = _push(t, *args)
        spans += got
    spans.append(("bench.read", t, t + 1e-3))
    return spans


SYNTHETIC = trace.Trace({}, _spans_fixture(), num_devices=1)

READERS = {"push_span_ms.ingest": 1.2, "dispatch_ms.ingest": 1.0,
           "frontier_ms.ingest": 0.05, "emit_span_ms.sat": 154.0,
           "emit_span_ms.rate": 154.0, "readback_ms.sat": 7.0,
           "readback_ms.rate": 7.0}


@pytest.mark.parametrize("name,want", sorted(READERS.items()))
def test_reader_on_synthetic_trace(name, want):
    assert SYNTHETIC.t0 == 0.0
    got = _reader(name).read(types.SimpleNamespace(trace=SYNTHETIC))
    assert got == pytest.approx(want, rel=1e-6)


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(os.path.dirname(__file__), "data",
                        "v5e_netflow.xplane.pb")
    return trace.reduce(path, num_devices=1)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_without_program_spans(name, recorded):
    """The recorded v5e trace predates the program's spans."""
    reader = _reader(name)
    assert reader.read(types.SimpleNamespace(trace=recorded)) is None
    assert reader.read(types.SimpleNamespace(trace=None)) is None


def test_spans_nest_by_interval():
    roots = _spans.program_spans(SYNTHETIC)
    assert [r.name for r in roots] == ["stream.push"] * 4
    closing = roots[-1]
    assert [sp.name for sp in closing.inner] == [
        "stream.dispatch", "stream.frontier", "stream.emit", "stream.emit"]
    assert [len(em.named("stream.readback"))
            for em in closing.named("stream.emit")] == [1, 1]


def test_idle_by_owner():
    spans = _spans_fixture()
    first = _spans.program_spans(SYNTHETIC)[0]
    disp, front = first.inner
    closing = _spans.program_spans(SYNTHETIC)[-1]
    rb = closing.named("stream.readback")[0]
    read_start = [s for n, s, _ in spans if n == "bench.read"][0]
    idle = [(0.0, first.start),                       # bench.push only
            (disp.end - 1e-5, front.start + 1e-5),    # dispatch, frontier
            (rb.start, rb.start + 2e-3),              # readback
            (closing.end, read_start + 5e-4),         # bench.push, read
            (read_start + 2e-3, read_start + 1e-2)]   # no span
    got = _spans.idle_by_owner(spans, 0.0, idle)
    want = {"bench.push": first.start + read_start - closing.end,
            "stream.dispatch": 1e-5, "stream.frontier": 1e-5,
            "stream.readback": 2e-3, "bench.read": 5e-4,
            "no span": 8e-3}
    assert got == pytest.approx(want, rel=1e-6)
    assert sum(got.values()) == pytest.approx(
        sum(e - s for s, e in idle), rel=1e-9)
