"""The paper's four workers on a mesh (``caida-netflow-4w``): a sound run
agrees with the numpy reference under the file's own limits and the
control and faults make ``correct`` false, on four forced CPU devices; the
readers of the mesh's collective and placement on hand-built traces; and
the byte count behind ``gather_roofline`` against the program's shapes."""
import importlib.util
import os

import pytest

import _bench_paths as bp

import faults
import ici
import run

_spec = importlib.util.spec_from_file_location(
    "bench_trace_mesh", os.path.join(bp.BENCH, "trace.py"))
trace = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(trace)

EXACT = ("closes_wrong", "accounting_wrong", "count_wrong", "sample_wrong",
         "retraces_in_window", "failed_closes")


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(bp.BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(hooks=None, seconds=6.0, seed=2**31 + 1501):
    # The file as the cell runs it, 4 times smaller per worker. Smaller
    # cells read the heavy-tailed TCP stratum's sample variance low, so
    # sound runs came near the file's ``bound_low`` limit, set from
    # full-size cells on four chips (at 8 times smaller up to 0.116).
    config, traffic = bp.shrink(bp.load("configs", "caida-netflow-4w"),
                                bp.load("traffic", "sat"), 4)
    assert (config["num_shards"], config["placement"]) == (4, "mesh")
    with (hooks or faults.Hooks()) as h:
        result, rows = run.measure({"name": "netflow4w.sat"}, config,
                                   traffic, [], [], seed, seconds, False,
                                   hooks=h, log=lambda *a, **k: None)
    return result, {n: (v, lim) for n, v, lim in rows}


def test_sound_run_agrees_with_reference():
    result, checks = _run(seconds=8.0)
    assert result["attempted"] >= 2 and result["failed"] == 0
    for name in EXACT:
        assert checks[name][0] == 0, (name, checks[name])
    assert result["correct"], checks


# (fault, a number it must fail): the half batch leaves every ring cell
# short of its arrivals; the exchange is seen in the emitted counts.
@pytest.mark.parametrize("fault,number", [
    ("keep_first", "fold_rank_z"),
    ("no_exchange", "count_wrong"),
    ("half_batch", "sample_wrong"),
])
def test_fault_makes_correct_false(fault, number):
    result, checks = _run(faults.ALL[fault]())
    value, limit = checks[number]
    assert value > limit, (number, checks)
    assert not result["correct"]


# -- the collective's readers on hand-built traces ---------------------------

def _mesh_trace(gather, closes=3, devices=4):
    """A trace of ``closes`` emission runs on each device, each holding
    one gather of ``gather(start) -> [(op, start, end)]``."""
    devs = {}
    for d in range(devices):
        modules, ops = [], []
        for c in range(closes):
            t = 0.1 * (c + 1) + 1e-3 * d
            modules.append(("jit_emit_iv", t, t + 0.05))
            ops.append(("jit_emit_iv/fusion.4", t, t + 0.04))
            ops += gather(t + 0.041)
        devs[d] = {"modules": modules, "ops": ops}
    return trace.Trace(devs, [("bench.push", 0.0, 1.0)], num_devices=devices)


def _async(t):
    return [("jit_emit_iv/all-gather-start.1", t, t + 1e-5),
            ("jit_emit_iv/all-gather-done.3", t + 1.5e-4, t + 2e-4)]


def _sync(t):
    return [("jit_emit_iv/all-gather.2", t, t + 5e-4)]


def _ctx(tr):
    import types
    return types.SimpleNamespace(
        trace=tr, config=bp.load("configs", "caida-netflow-4w"),
        device_kind="TPU v5 lite")


@pytest.mark.parametrize("gather,ms,names", [
    (_async, 0.2, ["jit_emit_iv/all-gather-done.3",
                   "jit_emit_iv/all-gather-start.1"]),
    (_sync, 0.5, ["jit_emit_iv/all-gather.2"]),
])
def test_gather_readers_on_a_mesh_trace(gather, ms, names):
    tr = _mesh_trace(gather)
    got = _reader("gather_ms.mesh").read(_ctx(tr))
    assert got == pytest.approx(ms, rel=1e-6)
    assert tr.matched["mesh collective"] == names
    share = _reader("gather_roofline").read(_ctx(tr))
    least = ici.closed_gather_bytes(4, 3, 26215) / (1600e9 / 8)
    assert share == pytest.approx(100 * least / (ms * 1e-3), rel=1e-6)
    assert 0 < share < 100


def test_gather_readers_none_without_a_gather():
    tr = _mesh_trace(lambda t: [])
    for name in ("gather_ms.mesh", "gather_roofline"):
        assert _reader(name).read(_ctx(tr)) is None
        assert _reader(name).read(_ctx(None)) is None


def test_closed_gather_bytes_against_the_program_shapes(key):
    from repro.runtime import PipelinedExecutor, QueryRegistry, RuntimeConfig
    # Each of the 3 other shards sends its 3 cells of the closed slot:
    # 26,215 f32 samples, the count and the taken number.
    assert ici.closed_gather_bytes(4, 3, 26215) == 3 * 3 * (4 * 26215 + 8)
    assert ici.closed_gather_bytes(1, 3, 26215) == 0
    w, k, s, n = 4, 4, 3, 40
    cfg = RuntimeConfig(num_strata=s, capacity=n * w, num_intervals=k,
                        num_shards=w, placement="mesh", emission="watermark")
    ex = PipelinedExecutor(cfg, QueryRegistry().register("mean", "mean"),
                           key)
    assert ex.state.window.intervals.values.shape[-1] == n
    # The program's gather carries the closed slot's rows among the whole
    # ring's, so a chip receives at least the closed interval's bytes.
    closed_rows = ici.closed_gather_bytes(w, s, n) // 4
    assert closed_rows == (w - 1) * s * (n + 2)
    assert (w - 1) * ex.gather_words() >= closed_rows


def test_unknown_device_has_no_ici_peak():
    assert ici.ici_bytes_per_s("TPU v5 lite") == 200e9
    with pytest.raises(KeyError, match="no interconnect peak"):
        ici.ici_bytes_per_s("TPU v9 imaginary")


# -- the placement span's reader -----------------------------------------------

def _push(t, place_ms):
    s = t + 1e-4
    end = s + 2e-3
    out = [("stream.push", s, end), ("stream.dispatch", s + 1e-5, s + 1e-3)]
    if place_ms is not None:
        out.append(("stream.place", s + 2e-5, s + 2e-5 + place_ms * 1e-3))
    out += [("stream.frontier", s + 1e-3, s + 1.1e-3),
            ("bench.push", t, end + 1e-5)]
    return out, end + 1e-4


def _spans(place):
    spans, t = [], 0.0
    for ms in place:
        got, t = _push(t, ms)
        spans += got
    return trace.Trace({}, spans, num_devices=1)


def test_place_reader_median_per_push():
    import types
    read = _reader("place_ms.mesh").read
    got = read(types.SimpleNamespace(trace=_spans([0.3, 0.5, 0.4, 0.9])))
    assert got == pytest.approx(0.45, rel=1e-6)
    assert read(types.SimpleNamespace(trace=_spans([None, None]))) is None
    assert read(types.SimpleNamespace(trace=None)) is None
