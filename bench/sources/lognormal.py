"""Log-normal payloads per stratum (CAIDA-like NetFlow flow bytes).

Copied from ``repro.stream.sources.NetflowSource.chunk`` in numpy:
stratum ``i`` is drawn with probability ``mix[i]`` and its value is
``exp(log_mu[i] + log_sigma[i] · N(0, 1))``. Kept here so that a later
change to the program's sources cannot move the benchmark's data.
"""
from __future__ import annotations

import numpy as np


def draw(rng: np.random.Generator, size: int, mix, values: dict):
    """``(values f32 [size], stratum_ids i32 [size])``."""
    sid = rng.choice(len(mix), size=size, p=mix).astype(np.int32)
    mu = np.asarray(values["log_mu"], np.float64)[sid]
    sg = np.asarray(values["log_sigma"], np.float64)[sid]
    vals = np.exp(mu + sg * rng.standard_normal(size))
    return vals.astype(np.float32), sid
