"""The random streams a trajectory consumes, re-derived from its key.

The program documents its streams (``repro.utils.prng.fold_in_time``):
per round ``t`` every component folds a tag and then ``t`` into the
trajectory's run key — tag 0 the hop, 2 the bursts, 4 the protocol's
coin flips, 6 the payload's local data. This module draws the same
uniforms with ``jax.random`` alone, so the reference shares the seeds'
meaning with the program and nothing else.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

MOVE, BURST, DECIDE, VISIT = 0, 2, 4, 6
CHUNK = 1000


def trajectory_key(base_key: int, seeds: int, index: int):
    """Run key of trajectory ``index`` of an ensemble of ``seeds`` from
    ``base_key``, and its init key: ``split(key(base), seeds)[index]``,
    split once more into (init, run)."""
    k = jax.random.split(jax.random.key(base_key), seeds)[index]
    k_init, k_run = jax.random.split(k)
    return k, k_init, k_run


def fold(key, tag, t):
    return jax.random.fold_in(jax.random.fold_in(key, tag), t)


def start_positions(k_init, slots: int, n: int) -> np.ndarray:
    return np.asarray(jax.random.randint(k_init, (slots,), 0, n, dtype=jnp.int32))


@functools.partial(jax.jit, static_argnames=("slots", "cols", "grid"))
def _chunk(k_run, t0, *, slots, cols, grid):
    def one(t):
        u_move = jax.random.uniform(fold(k_run, MOVE, t), (slots,))
        k_dec = fold(k_run, DECIDE, t)
        if grid:
            return u_move, jax.random.uniform(k_dec, (slots, slots))[:, :cols]
        k_fork, k_term = jax.random.split(k_dec)
        u = jnp.stack(
            [jax.random.uniform(k_fork, (slots,)), jax.random.uniform(k_term, (slots,))]
        )
        return u_move, u

    return jax.vmap(one)(t0 + jnp.arange(CHUNK, dtype=jnp.int32))


class Streams:
    """Per-round uniforms of one trajectory, fetched ``CHUNK`` rounds at a
    time: ``move[t]`` (W,), ``decide[t]`` — (2, W) fork/terminate coins, or
    (W, cols) for the MissingPerson grid — and ``burst(t, i)``."""

    def __init__(self, k_run, slots: int, grid_cols: int = 0):
        self.k_run, self.slots, self.cols = k_run, slots, grid_cols
        self._t0 = None

    def at(self, t: int):
        t0 = t - t % CHUNK
        if t0 != self._t0:
            move, dec = _chunk(
                self.k_run, jnp.int32(t0), slots=self.slots, cols=self.cols,
                grid=bool(self.cols),
            )
            self.move, self.dec = np.asarray(move), np.asarray(dec)
            self._t0 = t0
        return self.move[t - t0], self.dec[t - t0]

    def burst(self, t: int, i: int) -> np.ndarray:
        k = jax.random.fold_in(fold(self.k_run, BURST, jnp.int32(t)), i)
        return np.asarray(jax.random.uniform(k, (self.slots,)))
