"""Kernel-layer microbench: OASRS ingest + stats pass, jnp path vs the
Pallas path — interpreted on a CPU backend, compiled elsewhere
(``kernels/ops.interpret_mode``); rows name the lane that ran."""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit, param, time_call
from repro.core import oasrs, query
from repro.kernels import ops, ref

SPEC = jax.ShapeDtypeStruct((), jnp.float32)


def _lane() -> str:
    return "interpret" if ops.interpret_mode() else "compiled"


def _bench_reservoir_fold(rows):
    """The ingest hot-path kernel: Pallas ``reservoir_fold`` vs the numpy
    Algorithm-1 oracle vs the pure-jnp chunk fold — all three consume the
    SAME pre-drawn uniforms, so outputs are bit-identical and only the
    execution strategy is measured."""
    m, s, n = param(16_384, 2048), 32, 64
    key = jax.random.PRNGKey(7)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    sid = jax.random.randint(k1, (m,), 0, s)
    pay = jax.random.normal(k2, (m,))
    ua = jax.random.uniform(k3, (m,))
    us = jax.random.uniform(k4, (m,))
    mask = jnp.ones((m,), jnp.bool_)
    st0 = oasrs.init(s, n, SPEC, key)

    fold_jnp = jax.jit(oasrs.apply_chunk_uniforms)
    us_jnp = time_call(fold_jnp, st0, sid, pay, mask, ua, us,
                       warmup=1, iters=5)
    rows.append(emit("kernel.reservoir_fold.jnp", us_jnp,
                     f"items_per_sec={m / (us_jnp / 1e6):.0f}"))

    # Numpy oracle: the literal sequential loop (one timed pass).
    m_ref = param(16_384, 2048)
    t0 = time.perf_counter()
    ref.reservoir_fold_ref(sid[:m_ref], pay[:m_ref], ua[:m_ref],
                           us[:m_ref], mask[:m_ref], st0.counts,
                           st0.capacity, st0.values)
    us_ref = (time.perf_counter() - t0) * 1e6
    rows.append(emit("kernel.reservoir_fold.ref", us_ref,
                     f"items_per_sec={m_ref / (us_ref / 1e6):.0f}"))

    from repro.kernels.reservoir import reservoir_fold
    m_pl = param(2048, 512)
    fold_pl = functools.partial(reservoir_fold, block_m=512,
                                interpret=ops.interpret_mode())
    us_pl = time_call(fold_pl, sid[:m_pl], pay[:m_pl], ua[:m_pl],
                      us[:m_pl], mask[:m_pl], st0.counts, st0.capacity,
                      st0.values, warmup=1, iters=3)
    rows.append(emit(f"kernel.reservoir_fold.pallas_{_lane()}", us_pl,
                     f"items_per_sec={m_pl / (us_pl / 1e6):.0f}"))


def _bench_fold_layout(rows):
    """``reservoir_fold`` at the network-traffic deployment's widths
    (8,192-event chunks into 4 intervals x 3 strata of 26,215 slots).
    That ring is not a whole number of (8, 128) tiles, so each call pads
    it to 16 x 26,240 and slices the result back; an aligned ring of that
    size is folded in place. The two rows differ by what the pad costs."""
    from repro.kernels.reservoir import reservoir_fold
    m, g, cap = param(8192, 512), 12, 26_215
    key = jax.random.PRNGKey(11)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    sid = jax.random.randint(k1, (m,), 0, g)
    pay = jax.random.normal(k2, (m,))
    ua = jax.random.uniform(k3, (m,))
    us = jax.random.uniform(k4, (m,))
    mask = jnp.ones((m,), jnp.bool_)
    fold = jax.jit(functools.partial(reservoir_fold, block_m=512,
                                     interpret=ops.interpret_mode()),
                   donate_argnums=(5, 7))
    iters = param(50, 2)
    for name, (r, n) in (("padded_12x26215", (g, cap)),
                         ("aligned_16x26240", (16, 26_240))):
        values = jnp.zeros((r, n), jnp.float32)
        counts = jnp.zeros((r,), jnp.int32)
        capacity = jnp.full((r,), cap, jnp.int32)
        times = []
        for i in range(2 + iters):
            t0 = time.perf_counter()
            values, counts = jax.block_until_ready(
                fold(sid, pay, ua, us, mask, counts, capacity, values))
            if i >= 2:
                times.append(time.perf_counter() - t0)
        times.sort()
        us_call = times[len(times) // 2] * 1e6
        rows.append(emit(f"kernel.reservoir_fold.{name}_{_lane()}", us_call,
                         f"items_per_sec={m / (us_call / 1e6):.0f}"))


def run() -> list:
    rows = []
    m, s, n = param(65_536, 8192), 16, param(256, 64)
    key = jax.random.PRNGKey(0)
    sid = jax.random.randint(key, (m,), 0, s)
    x = jax.random.normal(jax.random.fold_in(key, 1), (m,))

    st0 = oasrs.init(s, n, SPEC, key)
    fold = jax.jit(oasrs.update_chunk)
    us = time_call(fold, st0, sid, x, warmup=1, iters=5)
    rows.append(emit("kernel.oasrs_fold.jnp", us,
                     f"items_per_sec={m / (us / 1e6):.0f}"))

    stats = jax.jit(lambda st: query.stats(st))
    st1 = fold(st0, sid, x)
    us = time_call(stats, st1, warmup=1, iters=5)
    rows.append(emit("kernel.stats_pass.jnp", us, ""))

    mom = jax.jit(lambda v, i: ops.stratum_moments(v, i, s,
                                                   use_pallas=False))
    us = time_call(mom, x, sid, warmup=1, iters=5)
    rows.append(emit("kernel.stratum_moments.ref", us,
                     f"items_per_sec={m / (us / 1e6):.0f}"))

    small = param(4096, 512)
    us = time_call(
        lambda: ops.stratum_moments(x[:small], sid[:small], s,
                                    use_pallas=True),
        warmup=1, iters=3)
    rows.append(emit(f"kernel.stratum_moments.pallas_{_lane()}", us,
                     f"items_per_sec={small / (us / 1e6):.0f}"))

    _bench_reservoir_fold(rows)
    _bench_fold_layout(rows)
    return rows


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    run()
