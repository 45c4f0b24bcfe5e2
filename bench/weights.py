"""Seeded random weights, made on the device in one jitted call, in bf16
and in the parameter layout the program serves.

The benchmark makes the weights itself, so that the plain reference
(``bench/references/``) and the program read the same numbers and the
reference takes nothing that the program made.  The layout (key names
and shapes) is the program's interface, and the configuration's
architecture module (``bench/architectures/<name>.py``) builds it with
the initialisers below.

Linear weights are normal with standard deviation ``fan_in ** -0.5``,
embeddings ``0.02``, biases ``0.02``, norm scales ``1 + 0.1 * normal``
(so that a norm applied without its scale shows).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.harness import architecture

BF16 = jnp.bfloat16


def linear(key, din, dout, bias):
    kw, kb = jax.random.split(key)
    p = {"w": (jax.random.normal(kw, (din, dout), jnp.float32)
               * din ** -0.5).astype(BF16)}
    if bias:
        p["b"] = (jax.random.normal(kb, (dout,), jnp.float32)
                  * 0.02).astype(BF16)
    return p


def norm(key, d):
    return {"scale": 1.0 + 0.1 * jax.random.normal(key, (d,), jnp.float32)}


def make_weights(config: dict, seed: int) -> dict:
    """All weights of the configuration ``config`` from ``seed``, on the
    default device."""
    build, model = architecture(config).make_weights, config["model"]
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             (seed >> 32) & 0xFFFFFFFF)
    fn = jax.jit(lambda k: build(model, k))
    return jax.block_until_ready(fn(key))
