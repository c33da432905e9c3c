"""Pallas TPU flash attention — the EBISU discipline applied to attention.

The dry-run roofline showed every *_4k/_32k LM cell memory-bound, dominated
by the pure-JAX chunked attention materializing its (qc × kc) score blocks to
HBM between the two dots (~half the step's byte traffic).  This kernel keeps
the query tile + running softmax statistics resident in VMEM while K/V stream
through — "one tile at a time, scale it to the scratchpad, stream the rest",
exactly the paper's §4.1/§4.3 execution model with attention scores playing
the role of the stencil's intermediate time steps:

  * grid = (batch·heads, q-chunks, kv-chunks); the kv axis is the sequential
    ("arbitrary") innermost dimension — a streaming queue;
  * VMEM scratch carries (acc, m, l) across kv steps — the circular-queue
    analogue (depth-1 ring: online softmax needs only the running state);
  * the output block is written once, on the last kv step — lazy streaming's
    one-sync-per-tile;
  * HBM traffic: q, k, v read once, o written once — no S×S materialization.

Supports causal & sliding-window masks and GQA (kv-head index_map h→h//G).
Validated in interpret mode against models/attention.dense_attention.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc, m, l, *, scale: float,
            causal: bool, window: int | None, qc: int, kc: int, nk: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        m[...] = jnp.full_like(m, NEG_INF)
        l[...] = jnp.zeros_like(l)

    q = q_ref[0].astype(jnp.float32)                 # (qc, hd)
    k = k_ref[0].astype(jnp.float32)                 # (kc, hd)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = iq * qc + jax.lax.broadcasted_iota(jnp.int32, (qc, kc), 0)
    kpos = ik * kc + jax.lax.broadcasted_iota(jnp.int32, (qc, kc), 1)
    ok = jnp.ones((qc, kc), jnp.bool_)
    if causal:
        ok &= kpos <= qpos
    if window is not None:
        ok &= kpos > qpos - window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m[:, :1]                                 # (qc, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l[:, :1] * corr + p.sum(axis=1, keepdims=True)
    acc[...] = acc[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m[...] = jnp.broadcast_to(m_new, m.shape)
    l[...] = jnp.broadcast_to(l_new, l.shape)

    @pl.when(ik == nk - 1)
    def _():
        o_ref[0] = (acc[...] / jnp.maximum(l[:, :1], 1e-30)).astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_chunk",
                                             "kv_chunk", "interpret"))
def flash_attention_pallas(q, k, v, *, causal=True, window=None,
                           q_chunk=256, kv_chunk=512,
                           interpret: bool = True):
    """q: (B, S, H, hd); k, v: (B, Sk, KV, hd) -> (B, S, H, hd)."""
    b, s, h, hd = q.shape
    _, sk, kv, _ = k.shape
    g = h // kv
    qc = min(q_chunk, s)
    kc = min(kv_chunk, sk)
    assert s % qc == 0 and sk % kc == 0, (s, qc, sk, kc)
    nq, nk = s // qc, sk // kc
    scale = 1.0 / math.sqrt(hd)

    qt = q.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    kt = k.transpose(0, 2, 1, 3).reshape(b * kv, sk, hd)
    vt = v.transpose(0, 2, 1, 3).reshape(b * kv, sk, hd)

    kern = functools.partial(_kernel, scale=scale, causal=causal,
                             window=window, qc=qc, kc=kc, nk=nk)
    out = pl.pallas_call(
        kern,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, qc, hd), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, kc, hd),
                         lambda bh, iq, ik, g=g: (bh // g, ik, 0)),
            pl.BlockSpec((1, kc, hd),
                         lambda bh, iq, ik, g=g: (bh // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, qc, hd), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((qc, hd), jnp.float32),
                        pltpu.VMEM((qc, 128), jnp.float32),
                        pltpu.VMEM((qc, 128), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return out.reshape(b, h, s, hd).transpose(0, 2, 1, 3)


def attention_hbm_bytes(b, s, sk, h, kv, hd, bytes_per_el=2) -> int:
    """Kernel HBM traffic: q,k,v read once + o written once (per call)."""
    return bytes_per_el * (b * s * h * hd * 2 + 2 * b * sk * kv * hd)


# ------------------------------------------------------------- backward ----
def _fwd_kernel_lse(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m, l, *,
                    scale, causal, window, qc, kc, nk):
    """Forward that also emits logsumexp (needed by the backward kernels)."""
    _kernel(q_ref, k_ref, v_ref, o_ref, acc, m, l, scale=scale,
            causal=causal, window=window, qc=qc, kc=kc, nk=nk)
    ik = pl.program_id(2)

    @pl.when(ik == nk - 1)
    def _():
        lse_ref[0] = (m[:, :1] + jnp.log(jnp.maximum(l[:, :1], 1e-30))
                      ).astype(lse_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *,
                scale, causal, window, qc, kc, nq, nk):
    """dq over the kv axis and dk/dv over the q axis, one fused grid.

    Grid: (batch*heads, nq, nk) with BOTH inner axes sequential; dq for a
    q-chunk accumulates across its nk steps (written at ik == nk-1); dk/dv
    for a kv-chunk accumulate across grid wrap-around of iq — realized by
    making the kv axis the middle (parallel-ish) axis would break the acc,
    so we keep (nq outer, nk inner) and accumulate dk/dv in a scratch the
    size of ONE kv chunk, flushing by += into HBM via input_output_aliasing-
    free accumulation: dk/dv refs are indexed by ik, so each (iq, ik) step
    adds its contribution with a read-modify-write under @pl.when(iq == 0)
    initialization.
    """
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0].astype(jnp.float32)          # (qc, 1)
    delta = delta_ref[0].astype(jnp.float32)      # (qc, 1)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    qpos = iq * qc + jax.lax.broadcasted_iota(jnp.int32, (qc, kc), 0)
    kpos = ik * kc + jax.lax.broadcasted_iota(jnp.int32, (qc, kc), 1)
    ok = jnp.ones((qc, kc), jnp.bool_)
    if causal:
        ok &= kpos <= qpos
    if window is not None:
        ok &= kpos > qpos - window
    p = jnp.where(ok, jnp.exp(s - lse), 0.0)      # (qc, kc)

    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale                 # (qc, kc)

    # ---- dq: accumulate over ik, flush at the last kv chunk -------------
    @pl.when(ik == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)
    dq_acc[...] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)

    # ---- dk/dv: accumulate over iq into HBM blocks indexed by ik --------
    dk_c = jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    dv_c = jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)

    @pl.when(iq == 0)
    def _():
        dk_ref[0] = jnp.zeros_like(dk_ref[0])
        dv_ref[0] = jnp.zeros_like(dv_ref[0])
    dk_ref[0] += dk_c.astype(dk_ref.dtype)
    dv_ref[0] += dv_c.astype(dv_ref.dtype)
    del dk_acc, dv_acc


def flash_attention_pallas_fwd(q, k, v, *, causal, window, q_chunk,
                               kv_chunk, interpret):
    b, s, h, hd = q.shape
    _, sk, kv, _ = k.shape
    g = h // kv
    qc, kc = min(q_chunk, s), min(kv_chunk, sk)
    nq, nk = s // qc, sk // kc
    scale = 1.0 / math.sqrt(hd)
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    kt = k.transpose(0, 2, 1, 3).reshape(b * kv, sk, hd)
    vt = v.transpose(0, 2, 1, 3).reshape(b * kv, sk, hd)
    kern = functools.partial(_fwd_kernel_lse, scale=scale, causal=causal,
                             window=window, qc=qc, kc=kc, nk=nk)
    out, lse = pl.pallas_call(
        kern,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, qc, hd), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, kc, hd), lambda bh, iq, ik, g=g: (bh // g, ik, 0)),
            pl.BlockSpec((1, kc, hd), lambda bh, iq, ik, g=g: (bh // g, ik, 0)),
        ],
        out_specs=[pl.BlockSpec((1, qc, hd), lambda bh, iq, ik: (bh, iq, 0)),
                   pl.BlockSpec((1, qc, 1), lambda bh, iq, ik: (bh, iq, 0))],
        out_shape=[jax.ShapeDtypeStruct((b * h, s, hd), q.dtype),
                   jax.ShapeDtypeStruct((b * h, s, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((qc, hd), jnp.float32),
                        pltpu.VMEM((qc, 128), jnp.float32),
                        pltpu.VMEM((qc, 128), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return out, lse


def flash_attention_pallas_bwd(q, k, v, do, out, lse, *, causal, window,
                               q_chunk, kv_chunk, interpret):
    b, s, h, hd = q.shape
    _, sk, kv, _ = k.shape
    g = h // kv
    qc, kc = min(q_chunk, s), min(kv_chunk, sk)
    nq, nk = s // qc, sk // kc
    scale = 1.0 / math.sqrt(hd)
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    kt = k.transpose(0, 2, 1, 3).reshape(b * kv, sk, hd)
    vt = v.transpose(0, 2, 1, 3).reshape(b * kv, sk, hd)
    dot = do.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    ot = out.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    delta = jnp.sum(dot.astype(jnp.float32) * ot.astype(jnp.float32),
                    axis=-1, keepdims=True)      # (b*h, s, 1)

    kern = functools.partial(_bwd_kernel, scale=scale, causal=causal,
                             window=window, qc=qc, kc=kc, nq=nq, nk=nk)
    dq, dk_h, dv_h = pl.pallas_call(
        kern,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, qc, hd), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, kc, hd), lambda bh, iq, ik, g=g: (bh // g, ik, 0)),
            pl.BlockSpec((1, kc, hd), lambda bh, iq, ik, g=g: (bh // g, ik, 0)),
            pl.BlockSpec((1, qc, hd), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, qc, 1), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, qc, 1), lambda bh, iq, ik: (bh, iq, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, qc, hd), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, kc, hd), lambda bh, iq, ik: (bh, ik, 0)),
            pl.BlockSpec((1, kc, hd), lambda bh, iq, ik: (bh, ik, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b * h, s, hd), jnp.float32),
                   jax.ShapeDtypeStruct((b * h, sk, hd), jnp.float32),
                   jax.ShapeDtypeStruct((b * h, sk, hd), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((qc, hd), jnp.float32),
                        pltpu.VMEM((kc, hd), jnp.float32),
                        pltpu.VMEM((kc, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt, dot, lse, delta)
    dq = dq.reshape(b, h, s, hd).transpose(0, 2, 1, 3).astype(q.dtype)
    # GQA: sum the per-query-head dk/dv over each kv group
    dk = dk_h.reshape(b, kv, g, sk, hd).sum(axis=2).transpose(0, 2, 1, 3)
    dv = dv_h.reshape(b, kv, g, sk, hd).sum(axis=2).transpose(0, 2, 1, 3)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention_trainable(q, k, v, causal=True, window=None,
                              q_chunk=256, kv_chunk=512, interpret=True):
    """Differentiable Pallas flash attention (fwd + bwd kernels)."""
    out, _ = flash_attention_pallas_fwd(
        q, k, v, causal=causal, window=window, q_chunk=q_chunk,
        kv_chunk=kv_chunk, interpret=interpret)
    b, s, h, hd = q.shape
    return out.reshape(b, h, s, hd).transpose(0, 2, 1, 3)


def _fa_fwd(q, k, v, causal, window, q_chunk, kv_chunk, interpret):
    out, lse = flash_attention_pallas_fwd(
        q, k, v, causal=causal, window=window, q_chunk=q_chunk,
        kv_chunk=kv_chunk, interpret=interpret)
    b, s, h, hd = q.shape
    o4 = out.reshape(b, h, s, hd).transpose(0, 2, 1, 3)
    return o4, (q, k, v, o4, lse)


def _fa_bwd(causal, window, q_chunk, kv_chunk, interpret, res, do):
    q, k, v, o4, lse = res
    dq, dk, dv = flash_attention_pallas_bwd(
        q, k, v, do, o4, lse, causal=causal, window=window,
        q_chunk=q_chunk, kv_chunk=kv_chunk, interpret=interpret)
    return dq, dk, dv


flash_attention_trainable.defvjp(_fa_fwd, _fa_bwd)
