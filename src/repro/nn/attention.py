"""GQA attention: dense, wavefront-chunked (flash-style), and decode paths.

The chunked path processes the lower-triangular (q_chunk x kv_chunk) tile
grid as a static *wavefront schedule* — exactly the tile walk a
weight-stationary systolic array performs (see core.planner): the chunk size
plays the role of the ArrayFlex pipeline-collapse factor k, trading the
number of sequential steps against per-step work.  core.planner.attention_plan
picks the chunk size with the paper's Eq.(6)-style analytical model.

The dense and decode paths' QK and PV contractions dispatch through the
GEMM substrate under the ``attn.qk`` / ``attn.pv`` site labels
(:func:`qk_scores` / :func:`pv_mix`): the planner's Eq.(6) table and the
executed attention kernels are joined on the same names as every
projection GEMM, and the arrayflex backend runs all (batch x kv-head)
products of a step in ONE expert-batched kernel launch.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import substrate
from repro.parallel import sharding
from repro.parallel.sharding import constrain

NEG_INF = -1e30


def qk_scores(qg, k, *, backend="xla", interpret=None):
    """Attention scores via the substrate (site ``attn.qk``).

    qg: (B, S, KV, g, D) grouped queries; k: (B, T, KV, D).  Returns fp32
    scores laid out (B, KV, g, S, T) — the ``bskgd,btkd->bkgst`` einsum,
    executed as a (B*KV)-batched GEMM with the g*S query rows streamed
    against each kv-head's K^T.  Unscaled: callers apply 1/sqrt(D).
    """
    B, S, KV, g, D = qg.shape
    T = k.shape[1]
    qb = qg.transpose(0, 2, 3, 1, 4).reshape(B * KV, g * S, D)
    kb = k.transpose(0, 2, 3, 1).reshape(B * KV, D, T)
    s = substrate.batched_gemm(qb, kb, site="attn.qk", backend=backend,
                               out_dtype=jnp.float32, interpret=interpret,
                               shard=sharding.batched_shard_ctx(B * KV))
    return s.reshape(B, KV, g, S, T)


def pv_mix(w, v, *, backend="xla", interpret=None):
    """Probability-weighted value mix via the substrate (site ``attn.pv``).

    w: (B, KV, g, S, T) attention weights (cast to v.dtype by callers);
    v: (B, T, KV, D).  Returns (B, S, KV, g, D) — the
    ``bkgst,btkd->bskgd`` einsum as a (B*KV)-batched GEMM.
    """
    B, KV, g, S, T = w.shape
    D = v.shape[-1]
    pb = w.reshape(B * KV, g * S, T)
    vb = v.transpose(0, 2, 1, 3).reshape(B * KV, T, D)
    o = substrate.batched_gemm(pb, vb, site="attn.pv", backend=backend,
                               interpret=interpret,
                               shard=sharding.batched_shard_ctx(B * KV))
    return o.reshape(B, KV, g, S, D).transpose(0, 3, 1, 2, 4)


def _causal_pairs(n_q: int, n_k: int, q_chunk: int, kv_chunk: int,
                  window: int, q_offset: int):
    """Static (qi, kj) tile list for the causal (optionally windowed) band."""
    pairs = []
    for i in range(n_q):
        row_lo = q_offset + i * q_chunk              # first global row
        row_hi = row_lo + q_chunk - 1                # last global row
        for j in range(n_k):
            col_lo = j * kv_chunk
            col_hi = col_lo + kv_chunk - 1
            if col_lo > row_hi:                      # strictly above diagonal
                continue
            if window and col_hi < row_lo - window:  # outside SWA band
                continue
            pairs.append((i, j))
    return np.asarray(pairs, dtype=np.int32)


def _block_mask(row0, col0, q_chunk, kv_chunk, window, causal):
    r = row0 + jnp.arange(q_chunk)[:, None]
    c = col0 + jnp.arange(kv_chunk)[None, :]
    ok = jnp.ones((q_chunk, kv_chunk), jnp.bool_)
    if causal:
        ok = ok & (c <= r)
    if window:
        ok = ok & (c > r - window)
    return ok


def dense_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                    kv_len=None, backend="xla", interpret=None):
    """q: (B,S,H,D), k/v: (B,T,KV,D).  fp32 softmax.  Returns (B,S,H,D).

    QK and PV dispatch through the substrate (``attn.qk``/``attn.pv``)."""
    B, S, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    g = H // KV
    qg = constrain(q.reshape(B, S, KV, g, D), "attn_q_seq")
    k = constrain(k, "attn_qkv")
    v = constrain(v, "attn_qkv")
    scale = 1.0 / math.sqrt(D)
    scores = qk_scores(qg, k, backend=backend, interpret=interpret) * scale
    scores = constrain(scores, "attn_scores_seq")
    r = q_offset + jnp.arange(S)[:, None]
    c = jnp.arange(T)[None, :]
    ok = jnp.ones((S, T), jnp.bool_)
    if causal:
        ok = ok & (c <= r)
    if window:
        ok = ok & (c > r - window)
    if kv_len is not None:
        ok = ok & (c < kv_len)
    scores = jnp.where(ok[None, None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = pv_mix(w, v, backend=backend, interpret=interpret)
    return out.reshape(B, S, H, D)


def chunked_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                      q_chunk=1024, kv_chunk=1024):
    """Sequence-sharded flash-style attention: q rows stay resident (sharded
    over the 'model' axis under SPMD), KV is scanned in chunks with an online
    softmax.  Memory is O(B*S_local*H*D + B*H*S_local*kv_chunk).

    The KV chunk size is the ArrayFlex pipeline-collapse analogue: fewer,
    larger sequential steps vs more, smaller ones (core.planner picks it).

    T need not divide ``kv_chunk``: K/V are zero-padded to the chunk grid
    and padded columns are masked out of the online softmax, so a prime KV
    length (e.g. T=4097) runs in ``ceil(T/kc)`` steps instead of collapsing
    to chunk=1 via a largest-divisor search.
    """
    B, S, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    g = H // KV
    kv_chunk = min(kv_chunk, T)
    n_k = -(-T // kv_chunk)
    if n_k * kv_chunk != T:
        pad = n_k * kv_chunk - T
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    scale = 1.0 / math.sqrt(D)
    qg = constrain(q.reshape(B, S, KV, g, D), "attn_q_seq")
    k = constrain(k, "attn_qkv")
    v = constrain(v, "attn_qkv")
    rows = q_offset + jnp.arange(S)                       # global row ids

    o = constrain(jnp.zeros((B, S, KV, g, D), jnp.float32), "attn_q_seq")
    m = constrain(jnp.full((B, S, KV, g), NEG_INF, jnp.float32),
                  "attn_stat_seq")
    l = constrain(jnp.zeros((B, S, KV, g), jnp.float32), "attn_stat_seq")

    def step(carry, j):
        o, m, l = carry
        col0 = j * kv_chunk
        ks = jax.lax.dynamic_slice_in_dim(k, col0, kv_chunk, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(v, col0, kv_chunk, axis=1)
        s = jnp.einsum("bskgd,btkd->bkgst", qg, ks,
                       preferred_element_type=jnp.float32) * scale
        cols = col0 + jnp.arange(kv_chunk)
        ok = jnp.ones((S, kv_chunk), jnp.bool_)
        if causal:
            ok = ok & (cols[None, :] <= rows[:, None])
        if window:
            ok = ok & (cols[None, :] > rows[:, None] - window)
        if n_k * kv_chunk != T:                  # zero-padded ragged tail
            ok = ok & (cols[None, :] < T)
        okb = ok[None, None, None]                         # (1,1,1,S,kc)
        s = jnp.where(okb, s, NEG_INF)
        blk_max = jnp.moveaxis(jnp.max(s, axis=-1), -1, 1)  # (B,S,KV,g)
        m_new = jnp.maximum(m, blk_max)
        p = jnp.exp(s - jnp.moveaxis(m_new, 1, -1)[..., None])
        p = jnp.where(okb, p, 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.moveaxis(jnp.sum(p, -1), -1, 1)
        pv = jnp.einsum("bkgst,btkd->bskgd", p.astype(vs.dtype), vs,
                        preferred_element_type=jnp.float32)
        o = o * corr[..., None] + pv
        return (o, m_new, l), None

    # remat each KV step: backward recomputes the (bq x kc) score block
    # instead of saving every per-step intermediate (O(n_k) x 4GiB at 90B
    # scale); only the (o, m, l) carries persist.
    (o, m, l), _ = jax.lax.scan(jax.checkpoint(step), (o, m, l),
                                jnp.arange(n_k))
    out = o / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(B, S, H, D).astype(q.dtype)


def attention(q, k, v, *, causal=True, window=0, q_offset=0,
              q_chunk=1024, kv_chunk=1024, dense_below=2048,
              backend="xla", interpret=None):
    """``backend``/``interpret`` apply to the dense path's substrate QK/PV
    dispatch only: above ``dense_below`` the chunked flash-style scan runs
    its own schedule, whose ArrayFlex-collapse analogue is the KV chunk
    picked by ``planner.attention_plan`` (see docs/substrate.md) — it has
    no substrate GEMMs or Pallas launches to configure."""
    if q.shape[1] <= dense_below:
        return dense_attention(q, k, v, causal=causal, window=window,
                               q_offset=q_offset, backend=backend,
                               interpret=interpret)
    return chunked_attention(q, k, v, causal=causal, window=window,
                             q_offset=q_offset, q_chunk=q_chunk,
                             kv_chunk=kv_chunk)


def gather_pages(pool, layer, block_tables, kv_heads):
    """Assemble per-sequence logical K/V views of one layer from the
    stacked physical page pool.

    pool: (n_layers, n_pages, page, KV*D), a token's heads side by side on
    one row; layer: int32 scalar (traced inside the layer scan);
    block_tables: (B, n_pg) int32 physical page ids.  The pages are
    gathered on (layer, page) straight from the stacked pool, so no
    per-layer copy of the pool is made.  Returns (B, n_pg*page, KV, D) —
    the contiguous cache view the existing ``attn.qk``/``attn.pv``
    substrate dispatches consume, so a paged cache feeds the *same* GEMM
    plans as the dense one (the view length equals the dense cache length
    by the engine's page|max_seq contract, which is what keeps paged
    decoding bit-identical)."""
    B, n_pg = block_tables.shape
    page, row = pool.shape[2:]
    return pool[layer, block_tables].reshape(
        B, n_pg * page, kv_heads, row // kv_heads)


def decode_attention(q, k_cache, v_cache, pos, *, window=0, backend="xla",
                     interpret=None):
    """Single-token attention against a (possibly ring-buffered) KV cache.

    q: (B,1,H,D); caches (B,T,KV,D); pos: scalar int32 OR per-sequence
    (B,) int32 (ragged continuous batching).  For ring buffers (window>0)
    the cache length T == window and all slots are valid once pos >= window.
    QK and PV dispatch through the substrate (``attn.qk``/``attn.pv``).
    """
    B, _, H, D = q.shape
    T, KV = k_cache.shape[1], k_cache.shape[2]
    g = H // KV
    qg = q.reshape(B, 1, KV, g, D)
    scale = 1.0 / math.sqrt(D)
    s = qk_scores(qg, k_cache, backend=backend, interpret=interpret) * scale
    idx = jnp.arange(T)
    pos_v = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    if window:
        valid = idx[None, :] < jnp.minimum(pos_v + 1, T)[:, None]
        valid = valid | (pos_v + 1 >= T)[:, None]          # ring full
    else:
        valid = idx[None, :] <= pos_v[:, None]
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(v_cache.dtype)
    out = pv_mix(w, v_cache, backend=backend, interpret=interpret)
    return out.reshape(B, 1, H, D).astype(q.dtype)
