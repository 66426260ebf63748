"""``chip_smoke.py`` off the chip: it refuses a CPU, and its phases (run
here directly, at a tiny scale, kernels interpreted) hold their own
checks — the four-run bitwise agreement, the numpy accounting reference,
obs conservation, exactly-once replay and the mesh-vs-vmap oracle."""
import importlib.util
import os
import sys

import pytest

_PATH = os.path.join(os.path.dirname(__file__), "..", "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod       # dataclasses resolve their module
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny(smoke):
    return smoke.Scale(chunk=256, chunks_per_interval=4, intervals=5,
                       kernel_block=128)


def test_refuses_to_run_without_a_tpu(smoke, capsys):
    assert smoke.main([]) == 1
    assert "no TPU found" in capsys.readouterr().err


def test_kernel_phase_tiny(smoke, tiny):
    smoke.kernel_phase(tiny, smoke.CompileClock())


def test_main_path_and_exactly_once_tiny(smoke, tiny):
    clock = smoke.CompileClock()
    cfg, chunks, fired = smoke.main_path_phase(tiny, clock)
    assert [e.interval for e, _ in fired] == [0, 1, 2, 3]
    smoke.exactly_once_phase(tiny, clock, cfg, chunks, fired)


def test_mesh4_phase_tiny(smoke, tiny):
    smoke.mesh4_phase(tiny, smoke.CompileClock())


def test_first_divergence_names_the_first_difference(smoke, tiny):
    import dataclasses
    import functools
    import jax
    from repro.runtime import PipelinedExecutor
    _, chunks = smoke.make_stream(tiny)
    cfg = smoke.runtime_config(tiny)
    key = jax.random.PRNGKey(0)
    make = functools.partial(PipelinedExecutor, cfg, smoke.registry())
    assert smoke.first_divergence(make, make, key, chunks[:3]) == (
        "no divergence on a chunk-by-chunk replay")
    smaller = functools.partial(
        PipelinedExecutor, dataclasses.replace(cfg, capacity=cfg.capacity - 1),
        smoke.registry())
    msg = smoke.first_divergence(make, smaller, key, chunks)
    assert msg.startswith("state after chunk 0 differs in")
    assert ".capacity" in msg


def test_reference_drops_events_behind_the_watermark(smoke, tiny):
    """The accounting reference is independent of the runtime: a stream
    whose events all land late of the watermark counts them dropped."""
    import dataclasses
    import jax.numpy as jnp
    _, chunks = smoke.make_stream(tiny)
    late = dataclasses.replace(chunks[1], times=jnp.zeros_like(
        chunks[1].times))
    ahead = dataclasses.replace(chunks[0], times=chunks[0].times + 2.0)
    ref = smoke.reference([ahead, late], 1.0, 0.5, 4)
    assert ref.cumulative[-1][2] == tiny.chunk      # all dropped
    assert ref.offered == 2 * tiny.chunk
