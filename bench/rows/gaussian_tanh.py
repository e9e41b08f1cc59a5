"""Seeded rows made on the device in one jitted call.

The rows stand in for a public tabular data set of the configuration's
width ``d``: standard normal features and a smooth noisy target, the
``rows`` the configuration file lists under ``assumed``:
tanh(x·w) + 0.5 sin x₀ + 0.1 noise, w ~ N(0, 1/d).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from harness.data import seed31


@partial(jax.jit, static_argnums=(1, 2, 3))
def _rows(key, n: int, m: int, d: int):
    kx, kw, ke = jax.random.split(key, 3)
    X = jax.random.normal(kx, (n + m, d), jnp.float32)
    w = jax.random.normal(kw, (d,), jnp.float32) / math.sqrt(d)
    y = (jnp.tanh(X @ w) + 0.5 * jnp.sin(X[:, 0])
         + 0.1 * jax.random.normal(ke, (n + m,), jnp.float32))
    return X[:n], y[:n], X[n:], y[n:]


def make(config: dict, seed: int):
    """``(X, y, Xt, yt)``: ``n_train`` training rows and ``n_test``
    held-out rows of width ``d``, float32, on the default device."""
    return _rows(jax.random.key(seed31(seed)), config["n_train"],
                 config["n_test"], config["d"])
