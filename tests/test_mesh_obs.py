"""What the program reports of the mesh: a push placed on a 4-shard mesh
opens one ``stream.place`` span inside its ``stream.dispatch``, a vmap push
none; the ``run_meta`` event carries the u32 words one device contributes
to the emission's all-gather, 0 off the mesh."""
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.obs import metrics as obm
from repro.obs import spans
from repro.obs.events import EventLog
from repro.runtime import (BatchedExecutor, PipelinedExecutor, QueryRegistry,
                           RuntimeConfig)
from repro.runtime.records import TimestampedChunk

W, K, S, N = 4, 4, 3, 24


def _cfg(placement, shards=W):
    return RuntimeConfig(num_strata=S, capacity=N * shards, num_intervals=K,
                         num_shards=shards, placement=placement,
                         emission="watermark")


def _chunks(shards, count=12, m=64):
    rng = np.random.default_rng(3)
    out = []
    for e in range(count):
        t = (e * m + np.arange(m, dtype=np.float32)) / np.float32(4 * m)
        shape = (shards, m)
        out.append(TimestampedChunk(
            values=rng.random(shape, dtype=np.float32),
            stratum_ids=rng.integers(0, S, shape).astype(np.int32),
            times=np.broadcast_to(t, shape).astype(np.float32),
            mask=np.ones(shape, bool)))
    return out


def _traced_spans(tmp_path, placement, executor=PipelinedExecutor):
    ex = executor(_cfg(placement), QueryRegistry().register("mean", "mean"),
                  jax.random.PRNGKey(0))
    chunks = _chunks(W)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for c in chunks:
            ex.push(c)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
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


def test_mesh_push_opens_one_place_inside_its_dispatch(tmp_path):
    ex, chunks, found = _traced_spans(tmp_path, "mesh")
    assert len(ex.emissions) >= 1
    dispatches = [sp for sp in found if sp[0] == spans.DISPATCH]
    assert len(dispatches) == len(chunks)
    for d in dispatches:
        assert len(_inside(d, found, spans.PLACE)) == 1
    assert len([sp for sp in found if sp[0] == spans.PLACE]) == len(chunks)


def test_vmap_push_opens_no_place(tmp_path):
    ex, chunks, found = _traced_spans(tmp_path, "vmap")
    assert len([sp for sp in found if sp[0] == spans.DISPATCH]) \
        == len(chunks)
    assert not [sp for sp in found if sp[0] == spans.PLACE]


def test_batched_flush_on_the_mesh_opens_one_place(tmp_path):
    ex, chunks, found = _traced_spans(tmp_path, "mesh", BatchedExecutor)
    flushes = len(chunks) // ex.cfg.batch_chunks
    assert len([sp for sp in found if sp[0] == spans.PLACE]) == flushes


@pytest.mark.parametrize("placement,shards", [
    ("mesh", W), ("vmap", W), ("vmap", 1)])
def test_run_meta_gather_words(placement, shards):
    log = EventLog()
    ex = PipelinedExecutor(_cfg(placement, shards),
                           QueryRegistry().register("mean", "mean"),
                           jax.random.PRNGKey(0),
                           telemetry=obm.Telemetry(log=log))
    meta, = [e for e in log.events if e["type"] == "run_meta"]
    oracle = PipelinedExecutor(_cfg("vmap", shards),
                               QueryRegistry().register("mean", "mean"),
                               jax.random.PRNGKey(0))
    assert meta["emit_cells"] == oracle.emit_cells()
    if placement != "mesh":
        assert meta["gather_words"] == 0
        return
    # [K·S, N+2] cell rows, then the aux words (lead key 2, slot→interval
    # K, liveness K, counts > 0 K·S) padded to one more row of N+2.
    aux = 2 + K + K + K * S
    assert aux <= N + 2
    assert meta["gather_words"] == (K * S + 1) * (N + 2)
    assert ex.gather_words() == meta["gather_words"]
