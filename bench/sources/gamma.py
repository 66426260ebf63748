"""Gamma payloads per stratum (DEBS'15-like taxi trip distances).

Copied from ``repro.stream.sources.TaxiSource.chunk`` in numpy: stratum
``i`` is drawn with probability ``mix[i]`` and its value is
``scale[i] · Gamma(shape[i])``. Kept here so that a later change to the
program's sources cannot move the benchmark's data.
"""
from __future__ import annotations

import numpy as np


def draw(rng: np.random.Generator, size: int, mix, values: dict):
    """``(values f32 [size], stratum_ids i32 [size])``."""
    sid = rng.choice(len(mix), size=size, p=mix).astype(np.int32)
    shp = np.asarray(values["shape"], np.float64)[sid]
    scl = np.asarray(values["scale"], np.float64)[sid]
    vals = scl * rng.gamma(shp)
    return vals.astype(np.float32), sid
