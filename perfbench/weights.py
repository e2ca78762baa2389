"""Seeded weights for a configuration, made by the benchmark in the
program's parameter layout (its family's ``make``), so the program and the
reference start from the same numbers and the reference takes nothing the
program made.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
          "float16": jnp.float16}


def seed_key(seed: int):
    """A PRNG key that keeps all 64 bits of ``seed`` (``jax.random.key``
    alone drops the high word)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def make_on_device(family, m, seed: int) -> dict:
    """``family.make`` at sizes ``m`` from ``seed``, drawn on the device in
    one jitted call."""
    return jax.jit(lambda k: family.make(m, k))(seed_key(seed))
