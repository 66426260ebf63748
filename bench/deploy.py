"""The system under test, built from a configuration file.

The configuration names the executor, the emission mode, the ring, the
lateness, the capacity, the placement and the standing queries; this module
turns them into the program's ``RuntimeConfig``, ``QueryRegistry`` and
executor, and wraps the generator's host arrays in its chunk type. It is the
only place the benchmark calls into the program.
"""
from __future__ import annotations


def _every_event(v):
    import jax.numpy as jnp
    return jnp.ones(v.shape, jnp.bool_)


def registry(config: dict):
    from repro.runtime import QueryRegistry
    reg = QueryRegistry()
    for q in config["queries"]:
        kw = {"window": q.get("window", "merged")}
        if q["kind"] == "count":
            kw["predicate"] = _every_event
        if q["kind"] == "quantile":
            kw["qs"] = tuple(q["qs"])
        reg.register(q["name"], q["kind"], **kw)
    return reg


def runtime_config(config: dict):
    from repro.runtime import RuntimeConfig
    shards = int(config["num_shards"])
    return RuntimeConfig(
        num_strata=int(config["num_strata"]),
        # The runtime splits the total over the shards (ceil per shard).
        capacity=int(config["capacity_per_stratum"]) * shards,
        num_intervals=int(config["ring_intervals"]),
        interval_span=float(config["interval_span"]),
        allowed_lateness=float(config["allowed_lateness"]),
        num_shards=shards,
        placement=config["placement"],
        emission=config["emission"])


def executor(config: dict, key):
    from repro.runtime import PipelinedExecutor
    if config["executor"] != "pipelined":
        raise ValueError(f"executor {config['executor']!r} is not driven "
                         "yet; the benchmark drives 'pipelined'")
    return PipelinedExecutor(runtime_config(config), registry(config), key)


def chunk(values, stratum_ids, times, mask):
    """The program's arrival unit around the generator's host arrays."""
    from repro.runtime.records import TimestampedChunk
    return TimestampedChunk(values=values, stratum_ids=stratum_ids,
                            times=times, mask=mask)


def traces(ex) -> int:
    """Traces of every compiled step of the executor so far (its retrace
    sentinels)."""
    return sum(s.traces for s in ex._sentinels.values())


def ring(ex):
    """Host copy of the reservoir ring: ``(values [W, K, S, N],
    counts [W, K, S], capacity [W, K, S], slot_interval [W, K])``."""
    import jax
    import numpy as np
    st = jax.device_get(ex.state)
    iv = st.window.intervals
    out = [np.asarray(a) for a in (iv.values, iv.counts, iv.capacity,
                                   st.slot_interval)]
    if ex.cfg.num_shards == 1:
        out = [a[None] for a in out]
    return out


def counters(ex) -> dict:
    """Per-stratum device counters, summed over shards."""
    from repro.obs import metrics as obm
    return obm.counters(ex.state.metrics)
