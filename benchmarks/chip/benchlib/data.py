"""Benchmark inputs made from ``--seed`` on the device.

The tensors are built one mode-0 slab at a time, so the device holds the
tensor and one slab of temporaries.
"""

from __future__ import annotations

import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp

HIGHEST = "highest"
_LETTERS = "abcdefgh"


@functools.partial(jax.jit, static_argnames=("shape", "rank", "noise"))
def _cp_factors(key, shape, rank, noise):
    keys = jax.random.split(key, len(shape) + 1)
    fs = [jax.random.normal(k, (s, rank)) for k, s in zip(keys, shape)]
    return fs, keys[-1], noise * math.sqrt(rank)


def low_rank_plus_noise(key, shape: Sequence[int], rank: int,
                        noise: float = 0.1) -> jax.Array:
    """A rank-``rank`` CP tensor plus Gaussian noise of relative size
    about ``noise``."""
    shape = tuple(int(s) for s in shape)
    fs, knoise, sigma = _cp_factors(key, shape, int(rank), float(noise))
    return _single(fs, knoise, sigma, shape)


@functools.partial(jax.jit, static_argnames=("shape",))
def _single(fs, knoise, sigma, shape):
    """sum_r f0[:, r] o f1[:, r] o ... plus ``sigma`` times Gaussian noise
    keyed by the mode-0 slab."""
    letters = _LETTERS[: len(shape) - 1]
    spec = "r," + ",".join(f"{c}r" for c in letters) + "->" + letters

    def slab(i):
        s = jnp.einsum(spec, fs[0][i], *fs[1:], precision=HIGHEST)
        z = jax.random.normal(jax.random.fold_in(knoise, i), shape[1:])
        return s + sigma * z

    return jax.lax.map(slab, jnp.arange(shape[0]))


@functools.partial(jax.jit, static_argnames=("shape", "core", "ranks",
                                             "scale_at_rank", "noise"))
def tucker_field(key, shape: Sequence[int], core: Sequence[int],
                 ranks: Sequence[int], scale_at_rank: float,
                 noise: float) -> jax.Array:
    """A field with a decaying multilinear spectrum, slab by slab: a
    Tucker tensor with orthonormal factors and a Gaussian core of extents
    ``core`` whose entries are scaled along each mode ``k`` by
    ``scale_at_rank ** (i / ranks[k])`` (the scale falls to
    ``scale_at_rank`` at index ``ranks[k]`` and on past it), plus Gaussian
    noise of relative size ``noise``."""
    n = len(shape)
    keys = jax.random.split(key, n + 2)
    g = jax.random.normal(keys[0], core)
    for k, (c, r) in enumerate(zip(core, ranks)):
        s = scale_at_rank ** (jnp.arange(c, dtype=jnp.float32) / r)
        g = g * s.reshape((1,) * k + (c,) + (1,) * (n - k - 1))
    fs = [jnp.linalg.qr(jax.random.normal(k, (s, c)))[0]
          for k, s, c in zip(keys[1:], shape, core)]
    sigma = noise * jnp.sqrt(jnp.sum(jnp.square(g)) / math.prod(shape))
    letters = _LETTERS[:n]
    outs = "jklmnopq"[: n - 1]
    spec = (letters + "," + letters[0] + ","
            + ",".join(o + c for o, c in zip(outs, letters[1:]))
            + "->" + outs)

    def slab(i):
        s = jnp.einsum(spec, g, fs[0][i], *fs[1:], precision=HIGHEST)
        z = jax.random.normal(jax.random.fold_in(keys[-1], i), shape[1:])
        return s + sigma * z

    return jax.lax.map(slab, jnp.arange(shape[0]))


@functools.partial(jax.jit, static_argnames=("shape", "rank"))
def init_factors(key, shape: Sequence[int], rank: int) -> list[jax.Array]:
    """CP initial factors: standard normal entries over sqrt(rank), one
    split key per mode."""
    keys = jax.random.split(key, len(shape))
    return [jax.random.normal(k, (d, rank)) / math.sqrt(rank)
            for k, d in zip(keys, shape)]


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (the driver's exceed 32 bits)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**31),
                              seed // 2**31 % 2**31)
