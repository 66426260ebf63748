"""The benchmark's load generator: host-resident chunks from ``--seed``.

Events start in host memory, as they do in any deployment, so the copy to
the device is part of the served path. Payloads come from a pool of chunks:
one fixed draw of the configuration's mix and value distributions
(``bench/sources/<distribution>.py``), shuffled by ``--seed``; each
push stamps event times for its stream offset on the arrival ramp of
``repro.stream.replay.ReplayableStream`` (offset ``e`` covers event times
``[e·span, (e+1)·span)``, ``span = chunk / events_per_interval``) and shifts
them backwards by a bounded disorder, also drawn per offset from a pool.
So stamping a chunk costs a few vector operations whatever the system's
speed, and the same seed gives the same stream at every offset.
"""
from __future__ import annotations

import importlib

import numpy as np

#: Payload chunks in the pool. Offset ``e`` takes payload ``e % POOL`` and
#: disorder ``e % SHIFTS``; the two periods are coprime.
POOL = 256
SHIFTS = 97
#: Seed of the one draw of payloads that every ``--seed`` reorders.
DATASET = 20170908


def seed_sequence(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([abs(int(seed)), stream])


class Generator:
    """Chunks ``(values, stratum_ids, times, mask)`` of shape ``[W, M]``
    (``[M]`` when the deployment has one shard)."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        source = importlib.import_module(
            f"sources.{config['values']['distribution']}")
        self.shards = int(config["num_shards"])
        self.chunk = int(traffic["chunk"])
        self.rate = float(config["events_per_interval"])  # per event unit
        self.span = self.chunk / self.rate
        shape = (POOL, self.shards, self.chunk)
        # Every seed streams the same events (one draw of the
        # configuration's distributions), in its own order: so the seed
        # changes arrivals, disorder and sampling, not the data set, and
        # heavy-tailed accuracy does not swing from seed to seed.
        vals, sids = source.draw(np.random.default_rng(DATASET),
                                 int(np.prod(shape)), config["mix"],
                                 config["values"])
        order = seed_sequence(seed, 0).permutation(vals.size)
        self.values = vals[order].reshape(shape)
        self.sids = sids[order].reshape(shape)
        ramp = (np.arange(self.chunk, dtype=np.float32)
                / np.float32(self.rate))
        shift = np.float32(traffic["disorder"]) * seed_sequence(
            seed, 1).random((SHIFTS, self.shards, self.chunk),
                            dtype=np.float32)
        self.offsets = ramp[None, None, :] - shift
        self.mask = np.ones((self.shards, self.chunk), bool)
        loop = traffic["loop"]
        if loop not in ("closed", "open"):
            raise ValueError(f"traffic loop must be 'closed' or 'open', "
                             f"not {loop!r}")
        if traffic.get("bursts"):
            raise ValueError(f"bursts are not generated yet: "
                             f"{traffic['bursts']}")
        self.period = None
        if loop == "open":
            # Wall seconds between chunk arrivals (all shards together).
            self.period = (self.events_per_chunk
                           / float(traffic["rate_events_per_s"]))

    @property
    def events_per_chunk(self) -> int:
        return self.chunk * self.shards

    def at(self, offset: int):
        """The chunk at stream offset ``offset``."""
        t0 = np.float32(offset * self.span)
        times = np.maximum(t0 + self.offsets[offset % SHIFTS],
                           np.float32(0))
        out = (self.values[offset % POOL], self.sids[offset % POOL],
               times, self.mask)
        if self.shards == 1:
            out = tuple(a[0] for a in out)
        return out

    def due(self, offset: int) -> float:
        """Seconds after the window opens at which chunk ``offset`` is due
        (open loop); ``None`` in a closed loop."""
        if self.period is None:
            return None
        return offset * self.period
