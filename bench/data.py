"""Corpus generators of the benchmark, made on the device from a seed.

Copies of ``mnist_like`` and ``gaussian_mixture`` as the program's
``repro.data.synthetic`` defines them, kept here so that a change to the
program cannot move the yardstick.  Each generator is one jitted call in
the type the program serves (float32).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits (the low and high words
    are folded in separately, so seeds above 2**32 stay distinct)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


@functools.partial(jax.jit, static_argnames=("n", "d", "n_classes"))
def mnist_like(key, *, n: int, d: int, n_classes: int = 10):
    """MNIST-shaped corpus: class templates plus an 8-dim deformation per
    class plus noise.  Returns (x (n, d) f32, labels (n,) i32)."""
    kt, kd, kl, kn = jax.random.split(key, 4)
    templates = jax.random.normal(kt, (n_classes, d)) * 2.0
    basis = jax.random.normal(kd, (n_classes, 8, d)) * 0.8
    labels = jax.random.randint(kl, (n,), 0, n_classes)
    coeff = jax.random.normal(jax.random.fold_in(kn, 1), (n, 8))
    x = templates[labels] + jnp.einsum("nk,nkd->nd", coeff, basis[labels])
    x = x + 0.3 * jax.random.normal(kn, (n, d))
    return x.astype(jnp.float32), labels


@functools.partial(jax.jit, static_argnames=("n", "d", "n_clusters", "sep",
                                             "scale"))
def gaussian_mixture(key, *, n: int, d: int, n_clusters: int,
                     sep: float = 6.0, scale: float = 1.0):
    """Isotropic clusters around random centres.  Returns (x, labels)."""
    kc, kx, kl = jax.random.split(key, 3)
    centers = jax.random.normal(kc, (n_clusters, d)) * sep / np.sqrt(2)
    labels = jax.random.randint(kl, (n,), 0, n_clusters)
    x = centers[labels] + jax.random.normal(kx, (n, d)) * scale
    return x.astype(jnp.float32), labels


GENERATORS = {"mnist_like": mnist_like, "gaussian_mixture": gaussian_mixture}


def corpus(cfg: dict, key, n: int | None = None):
    """The configuration's corpus (``n`` rows, default ``cfg["N"]``)."""
    gen = dict(cfg["generator"])
    fn = GENERATORS[gen.pop("name")]
    return fn(key, n=int(n or cfg["N"]), d=int(cfg["d"]), **gen)
