"""Dense f32 operators for the EbV no-pivot contract: entries uniform in
[-1, 1], each diagonal entry replaced by its row's absolute sum plus one
(strict row diagonal dominance).  A copy of the program's
``core.ebv.make_diagonally_dominant``, kept here so that the benchmark's
data cannot change with the program.  Right-hand sides are standard normal.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

STRUCTURE = "dense"


def operator(key, config):
    n = config["n"]
    a = jax.random.uniform(key, (n, n), jnp.float32, minval=-1.0, maxval=1.0)
    rowsum = jnp.sum(jnp.abs(a), axis=-1)
    return a.at[jnp.arange(n), jnp.arange(n)].set(rowsum + 1.0)


def rhs(key, config):
    return jax.random.normal(key, (config["n"],), jnp.float32)
