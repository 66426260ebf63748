"""Shared small utilities: PRNG plumbing, ranking, tree helpers, the
persistent compile cache."""
from __future__ import annotations

import dataclasses
import os
from typing import Any

import jax
import jax.numpy as jnp

Pytree = Any


#: Fixed cache location inside the checkout (git-ignored): the path is
#: part of what lets a later run on the same checkout find its entries.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses that
    directory and nothing is set here. Otherwise the cache lives at
    :data:`COMPILE_CACHE_DIR`. Call before the first compilation.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def tree_leading_dim(tree: Pytree) -> int:
    """Leading dimension shared by all leaves of ``tree``."""
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        raise ValueError("empty pytree")
    m = leaves[0].shape[0]
    for leaf in leaves:
        if leaf.shape[0] != m:
            raise ValueError(
                f"inconsistent leading dims: {leaf.shape[0]} vs {m}")
    return m


def rank_within_stratum(stratum_ids: jax.Array) -> jax.Array:
    """``r[j]`` = number of k<j with ``stratum_ids[k] == stratum_ids[j]``.

    Sort-based (O(M log M), O(M) memory) so it scales to large chunks and
    large stratum counts, unlike a one-hot cumsum.
    """
    m = stratum_ids.shape[0]
    order = jnp.argsort(stratum_ids, stable=True)          # group by stratum
    sorted_ids = stratum_ids[order]
    # Position within the sorted array minus the start of this id's group.
    idx = jnp.arange(m, dtype=jnp.int32)
    is_start = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), sorted_ids[1:] != sorted_ids[:-1]])
    group_start = jax.lax.cummax(jnp.where(is_start, idx, 0))
    rank_sorted = idx - group_start
    # Scatter ranks back to original positions.
    rank = jnp.zeros((m,), jnp.int32).at[order].set(rank_sorted)
    return rank


def bincount(stratum_ids: jax.Array, num_strata: int) -> jax.Array:
    """Static-shape bincount (int32)."""
    return jnp.zeros((num_strata,), jnp.int32).at[stratum_ids].add(1)


def fold_in_str(key: jax.Array, label: str) -> jax.Array:
    """Deterministically fold a string label into a PRNG key."""
    h = 0
    for ch in label:
        h = (h * 131 + ord(ch)) % (2**31 - 1)
    return jax.random.fold_in(key, h)


def pretty_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024.0:
            return f"{n:.2f}{unit}"
        n /= 1024.0
    return f"{n:.2f}PiB"


def dataclass_pytree(cls):
    """Register a dataclass as a JAX pytree (all fields are children)."""
    fields = [f.name for f in dataclasses.fields(cls)]
    jax.tree_util.register_dataclass(cls, data_fields=fields, meta_fields=[])
    return cls
