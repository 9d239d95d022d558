"""Seeds as the benchmark takes them: any non-negative whole number,
also one that 32 bits do not hold."""

from __future__ import annotations


def seed_key(seed: int):
    """A JAX key from ``seed``."""
    import jax
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed {seed} is negative")
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def rng(seed: int, stream: int = 0):
    """A NumPy generator from ``seed``; ``stream`` picks an independent
    sequence for another use of the same seed."""
    import numpy as np
    return np.random.default_rng([int(seed), int(stream)])
