"""Jitted public wrappers around the Pallas kernels.

Whether a kernel is compiled or interpreted follows the platform, and is
decided in ONE place (:func:`interpret_mode`): on a CPU backend the
kernel bodies run under the Pallas interpreter; on any other backend
they are compiled for real, and a kernel the compiler refuses raises.
``use_pallas=False`` selects the pure-jnp reference path (used by default
inside big jitted programs where the interpreter would be slow).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import oasrs
from repro.core.oasrs import OASRSState
from repro.kernels import ref
from repro.kernels import reservoir as _reservoir
from repro.kernels.stratified_stats import stratified_stats
from repro.kernels.weighted_hist import weighted_hist


def interpret_mode() -> bool:
    """Pallas interpret mode for every kernel call: exactly when JAX's
    default backend is the CPU. ``core/oasrs`` and every wrapper below
    route through here."""
    return jax.default_backend() == "cpu"


def stratum_moments(values: jax.Array, stratum_ids: jax.Array,
                    num_strata: int, mask: Optional[jax.Array] = None,
                    use_pallas: bool = True, block_m: int = 1024):
    """Fused per-stratum (count, Σx, Σx²) — kernel-backed when enabled."""
    if mask is None:
        mask = jnp.ones(values.shape, jnp.bool_)
    if use_pallas:
        return stratified_stats(values, stratum_ids, mask, num_strata,
                                block_m=block_m, interpret=interpret_mode())
    return ref.stratified_stats_ref(values, stratum_ids, mask, num_strata)


def weighted_histogram(values: jax.Array, stratum_ids: jax.Array,
                       weights: jax.Array, mask: jax.Array,
                       edges: jax.Array, num_strata: int,
                       use_pallas: bool = True, block_m: int = 256):
    """Fused per-(stratum, bin) weighted histogram — kernel-backed.

    Returns ``(whist [S, B], counts [S, B])``; ``whist`` is the HT-weighted
    mass per cell, ``counts`` the raw sampled-item tallies that feed the
    per-bin Eq. 6 indicator variance. ``use_pallas=False`` selects the
    pure-jnp oracle — what the query layer passes on CPU, where the
    Pallas interpreter would dominate large jitted programs.
    """
    if use_pallas:
        return weighted_hist(values, stratum_ids, weights, mask, edges,
                             num_strata, block_m=block_m,
                             interpret=interpret_mode())
    return ref.weighted_hist_ref(values, stratum_ids, weights, mask, edges,
                                 num_strata)


def oasrs_fold(state: OASRSState, stratum_ids: jax.Array,
               payload: jax.Array, mask: Optional[jax.Array] = None,
               block_m: int = 512) -> OASRSState:
    """Kernel-backed OASRS chunk fold for scalar payloads.

    Thin alias of ``oasrs.update_chunk(backend="pallas")`` — bitwise
    equal to the jnp backend (both consume the same uniform draws) and
    to the Algorithm-1 oracle given the same uniforms.
    """
    return oasrs.update_chunk(state, stratum_ids, payload, mask,
                              backend="pallas", block_m=block_m)


def one_shot_ingest(*args, **kwargs):
    """:func:`reservoir.one_shot_ingest` in the platform's mode — the
    whole accepted-item ingest path (watermark route → slot reset →
    (slot, stratum) cell → counter bump → replacement draw → ring write →
    obs counters) as ONE Pallas call. The runtime's
    ``RuntimeConfig.ingest="onekernel"`` path lands here."""
    return _reservoir.one_shot_ingest(*args, interpret=interpret_mode(),
                                      **kwargs)
