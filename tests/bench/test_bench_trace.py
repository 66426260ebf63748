"""The reduction from a profiler trace to device busy time, per-program
time, the idle share and the breakdown."""
import importlib.util
import os

import pytest

import _bench_paths as bp

# Loaded by path: the name ``trace`` is also a standard-library module.
_spec = importlib.util.spec_from_file_location(
    "bench_trace", os.path.join(bp.BENCH, "trace.py"))
trace = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(trace)


def _device(shift=0.0):
    ops = [("jit_core/reservoir_fold.1", 0.10, 0.20),
           ("jit_core/fusion.1", 0.15, 0.25),
           ("jit_emit_iv/fusion.5", 0.50, 0.90)]
    mods = [("jit_core", 0.10, 0.25), ("jit_emit_iv", 0.50, 0.90)]
    move = lambda evs: [(n, s + shift, e + shift) for n, s, e in evs]
    return {"modules": move(mods), "ops": move(ops)}


SPANS = [("bench.push", 0.0, 0.3), ("PjitFunction(core)", 0.05, 0.1),
         ("bench.read", 0.9, 1.0)]


def test_busy_union_idle_share_and_window():
    tr = trace.Trace({0: _device()}, SPANS, num_devices=1)
    assert tr.window_s == pytest.approx(1.0)
    # Overlapping ops count once: 0.10-0.25 and 0.50-0.90.
    assert tr.busy_s == pytest.approx(0.55)
    assert tr.idle_pct() == pytest.approx(45.0)


def test_busy_is_averaged_over_devices_used():
    devs = {0: _device(), 1: _device(shift=0.05), 2: _device(shift=9.0)}
    tr = trace.Trace(devs, SPANS, num_devices=2)
    assert sorted(tr.devices) == [0, 1]
    assert tr.busy_s == pytest.approx(0.55)


def test_per_module_and_per_op_time():
    tr = trace.Trace({0: _device()}, SPANS, num_devices=1)
    assert tr.modules(r"^jit_core$", label="step") == pytest.approx(
        (1, 0.15))
    assert tr.modules(r"^jit_emit(_iv)?$") == pytest.approx((1, 0.40))
    assert tr.ops(r"/reservoir_fold(\.\d+)?$", label="fold") == \
        pytest.approx((1, 0.10))
    assert tr.matched["step"] == ["jit_core"]
    assert tr.matched["fold"] == ["jit_core/reservoir_fold.1"]
    assert tr.ops(r"/all-gather") == (0, 0)


def test_breakdown_names_gaps_by_innermost_host_span():
    tr = trace.Trace({0: _device()}, SPANS, num_devices=1)
    b = tr.breakdown()
    assert b["device_ops"][0] == ["jit_emit_iv/fusion.5", pytest.approx(0.4)]
    gaps = b["idle_gaps"]
    assert [g[1] for g in gaps] == pytest.approx([0.25, 0.1, 0.1])
    assert gaps[0][0] == "no host span"
    assert {g[0] for g in gaps[1:]} == {"PjitFunction(core)", "bench.read"}


def test_names():
    assert trace.op_name("%fusion.5 = f32[10]{0} fusion(x)") == "fusion.5"
    assert trace.module_name("jit_core(5916387662564026451)") == "jit_core"
    assert trace.union_length([(0, 1), (0.5, 2), (3, 4)]) == 3


def test_recorded_v5e_trace():
    """A trace recorded on one v5e chip: four pushes of the network
    deployment, the last of which closes an interval."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "v5e_netflow.xplane.pb")
    tr = trace.reduce(path, num_devices=1)
    assert list(tr.devices) == [0]
    steps, step_s = tr.modules(r"^jit_core$")
    emits, emit_s = tr.modules(r"^jit_emit(_iv)?$")
    folds, fold_s = tr.ops(r"/reservoir_fold(\.\d+)?$")
    assert steps >= 4 and emits == 1 and folds == steps
    assert 0 < fold_s < step_s < emit_s
    assert 0 < tr.busy_s < tr.window_s
    assert 0 < tr.idle_pct() < 100
    assert len(tr.breakdown()["device_ops"]) == 10
