"""The control and the faults the correctness check must catch.

Each is planted under the timed path of one run (``run.measure(...,
hooks=...)``): the benchmark's own runs never plant one. ``control.py``
reads them on the chip at a cell's own size, to set each limit of
``check.py`` between what sound runs read and what these read; the tests
under ``tests/bench/`` see ``correct`` come out false for each at a small
size on the CPU. Use each as a context manager: it patches on entry and
restores the program on exit.
"""
from __future__ import annotations

import dataclasses

import numpy as np


class Hooks:
    """No fault: the run as the benchmark makes it."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def executor(self, ex) -> None:
        """Called once the executor is built, before it compiles."""

    def chunk(self, chunk):
        """Called on every chunk before ``push``."""
        return chunk

    def results(self, results):
        """Called on every emission's answers as they reach the host."""
        return results


class KeepFirst(Hooks):
    """The control: a fold that keeps each cell's first arrivals and never
    replaces (every acceptance draw set to 1), the skip-based fold that
    would tempt a faster ingest. It breaks the configuration's guarantee
    that each cell holds a uniform sample of its arrivals."""

    def __enter__(self):
        import jax.numpy as jnp
        from repro.core import oasrs
        from repro.kernels import reservoir
        self._saved = (reservoir.reservoir_fold, oasrs.apply_chunk_uniforms)
        fold, apply = self._saved

        def kernel_fold(sid, pay, u_accept, *rest, **kw):
            return fold(sid, pay, jnp.ones_like(u_accept), *rest, **kw)

        def jnp_fold(state, sid, pay, mask, u_accept, u_slot):
            return apply(state, sid, pay, mask, jnp.ones_like(u_accept),
                         u_slot)

        reservoir.reservoir_fold = kernel_fold
        oasrs.apply_chunk_uniforms = jnp_fold
        return self

    def __exit__(self, *exc):
        from repro.core import oasrs
        from repro.kernels import reservoir
        reservoir.reservoir_fold, oasrs.apply_chunk_uniforms = self._saved
        return False


class StateUnchanged(Hooks):
    """The ingest step returns the state it was given."""

    def executor(self, ex) -> None:
        ex._step = lambda state, chunk: state


class HalfBatch(Hooks):
    """Half of every chunk is left out (its second half masked off before
    the step); the estimates are taken over the rest."""

    def chunk(self, chunk):
        mask = np.array(chunk.mask, copy=True)
        mask[..., mask.shape[-1] // 2:] = False
        return dataclasses.replace(chunk, mask=mask)


class AnswersAltered(Hooks):
    """Every answer is altered where it is produced: each value times 1.5
    and each variance a quarter (the 95% bound halved)."""

    def results(self, results):
        return {name: dataclasses.replace(r, value=r.value * 1.5,
                                          variance=r.variance * 0.25)
                for name, r in results.items()}


class NoExchange(Hooks):
    """The exchange between chips is left out: the emission's all-gather
    is replaced by this chip's own cells and words, repeated for every
    shard."""

    def __enter__(self):
        import jax.numpy as jnp
        from repro.core import distributed as dist
        self._saved = dist.gather_cells

        def local_only(view, aux, axis_name, num_shards):
            import jax
            merged = jax.tree.map(
                lambda x: jnp.concatenate([x] * num_shards, axis=0), view)
            aux_all = jnp.broadcast_to(aux.astype(jnp.uint32)[None],
                                       (num_shards,) + aux.shape)
            return merged, aux_all

        dist.gather_cells = local_only
        return self

    def __exit__(self, *exc):
        from repro.core import distributed as dist
        dist.gather_cells = self._saved
        return False


#: Name -> fault, as ``control.py`` takes them. ``no_exchange`` only
#: exists across chips.
ALL = {"keep_first": KeepFirst, "state_unchanged": StateUnchanged,
       "half_batch": HalfBatch, "answers_altered": AnswersAltered,
       "no_exchange": NoExchange}
