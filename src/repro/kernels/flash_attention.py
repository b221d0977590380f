"""Fused flash-attention forward kernel (Pallas, TPU BlockSpec tiling).

This is the fusion that removes the dominant HBM-traffic term of the jnp
chunked attention (see EXPERIMENTS.md §Perf): scores/probabilities live in
VMEM only; HBM sees Q, K, V once and O once.

Layout: q (BH, S, D), k/v (BH, T, D) — callers fold batch x heads (GQA
callers repeat or fold kv heads).  Grid = (BH, S/bq); each step loads one q
row-block, loops the full KV in VMEM-resident chunks with an online softmax,
and writes one O block.  Causal + sliding-window masks are applied from
global row/col ids so the schedule skips nothing it shouldn't.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.runtime import resolve_interpret

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, kv_chunk: int, causal: bool,
            window: int, scale: float, kv_len: int):
    q = q_ref[0]                                  # (bq, D)
    bq, D = q.shape
    T = k_ref.shape[1]
    n_k = T // kv_chunk
    row0 = pl.program_id(1) * bq
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (bq, kv_chunk), 0)

    def body(j, carry):
        o, m, l = carry
        ks = k_ref[0, pl.ds(j * kv_chunk, kv_chunk), :]
        vs = v_ref[0, pl.ds(j * kv_chunk, kv_chunk), :]
        s = jax.lax.dot_general(
            q, ks, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, kc)
        cols = (j * kv_chunk
                + jax.lax.broadcasted_iota(jnp.int32, (bq, kv_chunk), 1))
        ok = jnp.ones((bq, kv_chunk), jnp.bool_)
        if causal:
            ok = ok & (cols <= rows)
        if window:
            ok = ok & (cols > rows - window)
        if kv_len != T:                          # zero-padded ragged tail
            ok = ok & (cols < kv_len)
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(ok, p, 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(
            p.astype(vs.dtype), vs, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_new = o * corr[:, None] + pv
        return o_new, m_new, l_new

    o0 = jnp.zeros((bq, D), jnp.float32)
    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    o, m, l = jax.lax.fori_loop(0, n_k, body, (o0, m0, l0))
    o_ref[0] = (o / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    bq: int = 128, kv_chunk: int = 128, interpret=None):
    """q: (BH, S, D); k/v: (BH, T, D).  Returns (BH, S, D).

    T need not divide ``kv_chunk``: K/V are zero-padded to the chunk grid
    and the kernel masks columns past the true length (so the planner's
    chunk pick runs as-is instead of degenerating via a divisor search).

    ``interpret=None`` resolves via :func:`repro.kernels.runtime
    .resolve_interpret` (compiled on TPU, interpreted elsewhere).
    """
    interpret = resolve_interpret(interpret)
    BH, S, D = q.shape
    T = k.shape[1]
    bq = min(bq, S)
    kv_chunk = min(kv_chunk, T)
    assert S % bq == 0
    Tp = -(-T // kv_chunk) * kv_chunk
    if Tp != T:
        k = jnp.pad(k, ((0, 0), (0, Tp - T), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Tp - T), (0, 0)))
    scale = 1.0 / math.sqrt(D)
    kernel = functools.partial(_kernel, kv_chunk=kv_chunk, causal=causal,
                               window=window, scale=scale, kv_len=T)
    return pl.pallas_call(
        kernel,
        grid=(BH, S // bq),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Tp, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, Tp, D), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        interpret=interpret,
    )(q, k, v)
