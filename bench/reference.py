"""The benchmark's plain reference: Jacobi steps by shift-and-add in float32.

One step is ``y[i] = sum_k w_k * x[i + o_k]`` over the configuration's taps
``(o_k, w_k)``, with zero Dirichlet boundaries: a cell outside the domain
reads as 0 at every step.  It is written from the configuration file alone,
imports nothing of the program under test, and runs no kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def taps_of(config: dict) -> tuple[tuple[tuple[int, ...], float], ...]:
    """The configuration's taps as ``((offset, weight), ...)``."""
    return tuple((tuple(int(o) for o in t["offset"]), float(t["weight"]))
                 for t in config["taps"])


def shifted(x: jnp.ndarray, offset: tuple[int, ...]) -> jnp.ndarray:
    """``out[i] = x[i + offset]`` where that lies in the domain, else 0."""
    r = max(abs(o) for o in offset) if any(offset) else 0
    if r == 0:
        return x
    xp = jnp.pad(x, r)
    return xp[tuple(slice(r + o, r + o + n) for o, n in zip(offset, x.shape))]


def step(x: jnp.ndarray, taps, dtype=jnp.float32) -> jnp.ndarray:
    acc = jnp.zeros(x.shape, dtype)
    for offset, weight in taps:
        acc = acc + jnp.asarray(weight, dtype) * shifted(x, offset)
    return acc


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def run(x: jnp.ndarray, taps, steps: int, dtype=jnp.float32) -> jnp.ndarray:
    """``steps`` plain Jacobi steps of ``x``, held and computed in ``dtype``
    (float32; the control passes bfloat16), returned as float32."""
    v = jax.lax.fori_loop(0, steps, lambda _, v: step(v, taps, dtype),
                          x.astype(dtype))
    return v.astype(jnp.float32)


@jax.jit
def max_abs_err(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """The widest gap between two fields; NaN where either holds one."""
    return jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))
