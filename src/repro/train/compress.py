"""Gradient compression for cross-pod (DCN) reductions.

The 'pod' axis of the production mesh crosses data-center networking, an
order of magnitude slower than ICI — the cross-pod gradient all-reduce is
the one collective worth compressing (DESIGN.md §5).  This module provides
an int8 stochastic-rounding quantized psum:

    q = clip(round_sr(x / scale), -127, 127)      scale = max|x| / 127
    y = dequant(psum(q)) · psum happens on int32 to avoid overflow

Stochastic rounding keeps the estimator unbiased (E[q·scale] = x), so SGD
convergence is preserved in expectation; the wire moves 1 byte/grad instead
of 4 (f32) or 2 (bf16).  ``compressed_psum_tree`` applies it leaf-wise with
per-leaf scales; exact-zero leaves stay exact.

Used by ``make_compressed_allreduce_step`` — a shard_map data-parallel
wrapper demonstrating the pattern end-to-end (tests/multidev_compress_child
checks the quantization error bound and training parity on 8 devices).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _stochastic_round(x, key):
    lo = jnp.floor(x)
    frac = x - lo
    return lo + (jax.random.uniform(key, x.shape) < frac).astype(x.dtype)


def quantize_int8(x, key):
    """x -> (int8 codes, f32 scale), unbiased under stochastic rounding."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)).astype(jnp.float32), 1e-30) / 127.0
    q = _stochastic_round(x.astype(jnp.float32) / scale, key)
    return jnp.clip(q, -127, 127).astype(jnp.int8), scale


def dequantize(q, scale):
    return q.astype(jnp.float32) * scale


def compressed_psum(x, axis_name, key):
    """Quantized all-reduce over ``axis_name``: int8 on the wire (psum in
    int32), scales max-combined. Returns the f32 mean-preserving sum."""
    # decorrelate rounding noise across shards (keeps unbiasedness)
    key = jax.random.fold_in(key, jax.lax.axis_index(axis_name))
    q, scale = quantize_int8(x, key)
    # a shared scale keeps the sum linear: use the max scale across shards
    scale = jax.lax.pmax(scale, axis_name)
    q = _stochastic_round(x.astype(jnp.float32) / scale, key)
    q = jnp.clip(q, -127, 127).astype(jnp.int8)
    total = jax.lax.psum(q.astype(jnp.int32), axis_name)
    return total.astype(jnp.float32) * scale


def compressed_psum_tree(tree, axis_name, key):
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(key, max(1, len(leaves)))
    out = [compressed_psum(leaf, axis_name, k)
           for leaf, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, out)


def make_compressed_allreduce_step(loss_fn, mesh, axis_name="data",
                                   lr: float = 1e-2):
    """Data-parallel SGD step with an int8-compressed gradient all-reduce —
    the demonstration harness for the DCN-compression pattern (in the full
    trainer the same compressed_psum_tree slots in for the 'pod' axis)."""
    n = mesh.shape[axis_name]

    def step(params, batch, key):
        def local_loss(p, b):
            return loss_fn(p, b)
        grads = jax.grad(local_loss)(params, batch)
        grads = compressed_psum_tree(grads, axis_name, key)
        grads = jax.tree.map(lambda g: g / n, grads)
        return jax.tree.map(lambda p, g: p - lr * g.astype(p.dtype),
                            params, grads)

    # jitted: called eagerly, shard_map re-traces the step on every call
    return jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(), P(axis_name), P()),
        out_specs=P(), check_vma=False))
