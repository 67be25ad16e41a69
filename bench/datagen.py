"""Corpus and query pool, made on the device from the run's seed.

A JAX copy of the program's ``data.pipeline.synthetic_vectors`` and
``synthetic_queries``: a Gaussian mixture of ``n_modes`` modes whose
per-dimension scales decay as exp(-decay * d), turned by a random orthogonal
rotation so that the informative directions are not axis-aligned; queries
are corpus rows plus ``query_jitter`` standard deviations of per-dimension
noise.  The benchmark keeps its own copy so that its data cannot move with
the program, and makes the rows on the device so that a million of them
cost no host time.  The mixture itself is fixed by the configuration, so
that the work a run does (how much the screen prunes) does not swing with
the seed; the rows and queries drawn from it come from the seed.  Each
chip's block of rows comes from its own key, so a four-chip corpus is four
one-chip blocks of the same mixture.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def root_key(seed: int) -> jax.Array:
    """A threefry key from any whole-number seed (64 bits and more)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jnp.asarray(words, jnp.uint32)


@partial(jax.jit, static_argnames=("rows", "dim", "n_modes", "decay"))
def _block(mix_key, row_key, *, rows: int, dim: int, n_modes: int, decay: float):
    k_cent, k_rot = jax.random.split(mix_key)
    scales = jnp.exp(-decay * jnp.arange(dim, dtype=jnp.float32))
    centers = jax.random.normal(k_cent, (n_modes, dim), jnp.float32) * scales * 2
    rot, _ = jnp.linalg.qr(jax.random.normal(k_rot, (dim, dim), jnp.float32))
    k_mode, k_x = jax.random.split(row_key)
    mode = jax.random.randint(k_mode, (rows,), 0, n_modes)
    x = jax.random.normal(k_x, (rows, dim), jnp.float32) * scales + centers[mode]
    return jnp.matmul(x, rot, precision=HIGHEST)


def corpus_blocks(seed: int, devices, *, rows_per_chip: int, dim: int,
                  n_modes: int, decay: float, mixture_seed: int) -> list[jax.Array]:
    """One (rows_per_chip, dim) float32 block on each device.  The mixture
    (its centres and its rotation) comes from the configuration's
    ``mixture_seed``, so every run serves the same kind of corpus; the rows
    drawn from it come from ``seed``."""
    mix = root_key(mixture_seed)
    rows = root_key(seed)
    return [_block(jax.device_put(mix, d),
                   jax.device_put(jax.random.fold_in(rows, c), d),
                   rows=rows_per_chip, dim=dim, n_modes=n_modes, decay=decay)
            for c, d in enumerate(devices)]


@partial(jax.jit, static_argnames=("n", "jitter"))
def _queries(key, block, *, n: int, jitter: float):
    k_pick, k_noise = jax.random.split(key)
    base = block[jax.random.randint(k_pick, (n,), 0, block.shape[0])]
    sd = jnp.std(block, axis=0, keepdims=True)
    return base + jax.random.normal(k_noise, base.shape, jnp.float32) * jitter * sd


def query_pool(seed: int, blocks: list[jax.Array], *, n: int,
               jitter: float) -> np.ndarray:
    """(n, dim) float32 host queries near rows of every block in turn (the
    per-dimension spread is each block's own; all blocks share one mixture).
    """
    key = jax.random.fold_in(root_key(seed), 7)
    per = [n // len(blocks) + (c < n % len(blocks)) for c in range(len(blocks))]
    parts = [np.asarray(_queries(jax.device_put(jax.random.fold_in(key, c),
                                                b.devices().pop()), b,
                                 n=m, jitter=jitter))
             for c, (b, m) in enumerate(zip(blocks, per))]
    order = np.random.default_rng(np.random.SeedSequence(int(seed))).permutation(n)
    return np.concatenate(parts)[order].astype(np.float32)
