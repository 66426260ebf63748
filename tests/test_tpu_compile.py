"""Compile rehearsals: the Pallas kernels and the ingest step, compiled for
a described TPU v5e chip at the widths ``chip_smoke.py`` runs.

Nothing runs here; the TPU compiler (installed with JAX) compiles for a
chip that is described, not attached, and raises what the chip's
compiler would raise — a scalar store to VMEM, an unsupported relayout,
an i1 memory block, a block over the scoped VMEM limit. Interpret-mode
parity tests cannot see any of those.

The topology is described inside a module-scoped fixture (never at
import): only one process may load the TPU library at a time, and every
test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import reservoir as rk
from repro.kernels.stratified_stats import stratified_stats
from repro.kernels.weighted_hist import weighted_hist
from repro.runtime import RuntimeConfig, init_state
from repro.runtime.executor import _ingest_chunk
from repro.runtime.records import TimestampedChunk

# chip_smoke.py's network-traffic widths: 8,192-event chunks, 3 strata,
# a 4-interval ring, 26,215 = ceil(0.6 · 131,072 / 3) slots per stratum.
M, K, S, N = 8192, 4, 3, 26215
G = K * S                        # merged sample cells


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # Describing the chip loads the TPU compiler, which otherwise keeps
    # its logs in a fixed directory outside the checkout.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """``shape, dtype -> ShapeDtypeStruct`` on the described chip 0, with
    the persistent compile cache off (a compile for a described chip is
    written to it but can never be read back without the chip)."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_reservoir_fold_compiles_for_v5e(chip):
    f32, i32 = jnp.float32, jnp.int32
    _assert_kernel(rk.reservoir_fold.lower(
        chip((M,), i32), chip((M,), f32), chip((M,), f32), chip((M,), f32),
        chip((M,), jnp.bool_), chip((G,), i32), chip((G,), i32),
        chip((G, N), f32), block_m=512, interpret=False).compile())


def test_one_shot_ingest_compiles_for_v5e(chip):
    f32, i32 = jnp.float32, jnp.int32
    scalar = lambda dtype: chip((), dtype)
    _assert_kernel(rk.one_shot_ingest.lower(
        chip((M,), f32), chip((M,), i32), chip((M,), f32),
        chip((M,), jnp.bool_), chip((M,), f32), chip((M,), f32),
        max_time=scalar(f32), open_interval=scalar(i32),
        on_time=scalar(i32), late=scalar(i32), dropped=scalar(i32),
        chunks=scalar(i32), items=scalar(i32),
        slot_interval=chip((K,), i32), adopt=chip((S,), i32),
        counts=chip((K, S), i32), capacity=chip((K, S), i32),
        values=chip((K, S, N), f32), counters=chip((6, S), i32),
        span=1.0, allowed_lateness=0.5, interpret=False).compile())


def test_stratified_stats_compiles_for_v5e(chip):
    _assert_kernel(stratified_stats.lower(
        chip((G * N,), jnp.float32), chip((G * N,), jnp.int32),
        chip((G * N,), jnp.bool_), G, block_m=1024,
        interpret=False).compile())


def test_weighted_hist_compiles_for_v5e(chip):
    f32 = jnp.float32
    _assert_kernel(weighted_hist.lower(
        chip((G * N,), f32), chip((G * N,), jnp.int32), chip((G * N,), f32),
        chip((G * N,), jnp.bool_), chip((33,), f32), G, block_m=256,
        interpret=False).compile())


@pytest.mark.parametrize("ingest,backend,shards,kernel", [
    ("fused", None, 1, True),        # the TPU default: compiled fold
    ("fused", "jnp", 1, False),
    ("onekernel", None, 1, True),
    ("fused", None, 4, True),        # placement="vmap": vmapped kernel
])
def test_ingest_step_compiles_for_v5e(chip, monkeypatch, ingest, backend,
                                      shards, kernel):
    """The executors' per-chunk step at N_max = 26,215, traced as on the
    chip: code that asks the platform sees a TPU, so the default fold is
    the compiled kernel."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = RuntimeConfig(num_strata=S, capacity=N, num_intervals=K,
                        num_shards=shards, ingest=ingest, backend=backend,
                        emission="watermark")
    state = jax.tree.map(lambda x: chip(x.shape, x.dtype),
                         init_state(cfg, jax.random.PRNGKey(0)))
    lead = () if shards == 1 else (shards,)
    per = M // shards
    chunk = TimestampedChunk(
        values=chip(lead + (per,), jnp.float32),
        stratum_ids=chip(lead + (per,), jnp.int32),
        times=chip(lead + (per,), jnp.float32),
        mask=chip(lead + (per,), jnp.bool_))
    step = (lambda st, ch: _ingest_chunk(cfg, st, ch)) if shards == 1 \
        else jax.vmap(lambda st, ch: _ingest_chunk(cfg, st, ch))
    compiled = jax.jit(step, donate_argnums=0).lower(state, chunk).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == kernel
