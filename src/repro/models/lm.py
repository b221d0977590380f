"""Unified LM model family: dense / MoE / hybrid(Jamba) / SSM / VLM / audio.

Layers are grouped into *super-blocks* of ``period(cfg)`` sub-layers; every
super-block has identical structure, so the stack of ``n_layers/period``
super-blocks is executed with ``jax.lax.scan`` (one layer's HLO regardless of
depth — essential for 100-layer dry-runs) and optionally rematerialized.

Param/caches are plain pytrees; leaves of ``blocks``/``enc_blocks`` carry a
leading ``n_super`` stack dim consumed by the scan.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, SSMConfig
from repro.kernels import substrate
from repro.nn import layers, attention as attn_lib, moe as moe_lib, mamba as mamba_lib
from repro.parallel import sharding
from repro.parallel.sharding import constrain


# ---------------------------------------------------------------------------
# structure helpers

def _lcm(a, b):
    return a * b // math.gcd(a, b)


def period(cfg: ModelConfig) -> int:
    p = 1
    if cfg.family == "hybrid":
        p = _lcm(p, cfg.hybrid_period)
    if cfg.family == "vlm":
        p = _lcm(p, cfg.cross_attn_every)
    if cfg.moe is not None:
        p = _lcm(p, cfg.moe.moe_every)
    assert cfg.n_layers % p == 0, (cfg.n_layers, p)
    return p


def n_super(cfg: ModelConfig) -> int:
    return cfg.n_layers // period(cfg)


def sublayer_kind(cfg: ModelConfig, pos: int) -> dict:
    return dict(
        mixer="attn" if cfg.is_attn_layer(pos) else "mamba",
        # every audio (whisper) decoder layer cross-attends to the encoder
        cross=cfg.is_cross_attn_layer(pos) or cfg.family == "audio",
        mlp=("moe" if cfg.is_moe_layer(pos) else
             ("dense" if cfg.d_ff else None)),
    )


def cross_len(cfg: ModelConfig) -> int:
    return (cfg.n_image_tokens if cfg.family == "vlm"
            else cfg.max_source_positions)


def _cdtype(cfg):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg.compute_dtype]


def _pdtype(cfg):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg.param_dtype]


def cache_len(cfg: ModelConfig, max_seq: int) -> int:
    return min(cfg.sliding_window, max_seq) if cfg.sliding_window else max_seq


# ---------------------------------------------------------------------------
# attention sub-module

def attn_init(key, cfg: ModelConfig, dtype):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    H, KV = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    p = {
        "wq": layers.linear_init(ks[0], d, H * hd, bias=cfg.qkv_bias,
                                 dtype=dtype),
        "wk": layers.linear_init(ks[1], d, KV * hd, bias=cfg.qkv_bias,
                                 dtype=dtype),
        "wv": layers.linear_init(ks[2], d, KV * hd, bias=cfg.qkv_bias,
                                 dtype=dtype),
        "wo": layers.linear_init(ks[3], H * hd, d, dtype=dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = layers.rmsnorm_init(hd, dtype)
        p["k_norm"] = layers.rmsnorm_init(hd, dtype)
    return p


def _proj_qkv(p, x, kv_src, cfg, cd, norm_scale=None):
    B, S = x.shape[0], x.shape[1]
    hd, H, KV = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    be, ip = cfg.gemm_backend, cfg.pallas_interpret
    cross = kv_src is not None
    # ``norm_scale`` (the ln1 scale, self-attention only): the sublayer
    # hands rmsnorm_normalize'd x here and the scale fuses into each
    # projection's kernel prologue
    q = layers.linear(p["wq"], x, cd,
                      site="xattn.wq" if cross else "attn.wq",
                      backend=be, interpret=ip,
                      norm_scale=norm_scale).reshape(B, S, H, hd)
    src = x if kv_src is None else kv_src
    T = src.shape[1]
    # the planner fuses cross-attention K/V into one "xattn.kv" GEMM
    k = layers.linear(p["wk"], src, cd,
                      site="xattn.kv" if cross else "attn.wk",
                      backend=be, interpret=ip,
                      norm_scale=norm_scale).reshape(B, T, KV, hd)
    v = layers.linear(p["wv"], src, cd,
                      site="xattn.kv" if cross else "attn.wv",
                      backend=be, interpret=ip,
                      norm_scale=norm_scale).reshape(B, T, KV, hd)
    if cfg.qk_norm:                        # per head, before RoPE
        q = layers.rmsnorm(p["q_norm"], q, cfg.rms_eps)
        k = layers.rmsnorm(p["k_norm"], k, cfg.rms_eps)
    return q, k, v


def attn_full(p, x, cfg: ModelConfig, positions, *, causal=True,
              kv_src=None, norm_scale=None):
    """Train/prefill attention.  Returns (out, (k, v)) with rope'd keys."""
    cd = _cdtype(cfg)
    q, k, v = _proj_qkv(p, x, kv_src, cfg, cd, norm_scale)
    if kv_src is None:                     # self-attention -> RoPE
        q = layers.apply_rope(q, positions, cfg.rope_theta)
        k = layers.apply_rope(k, positions, cfg.rope_theta)
    out = attn_lib.attention(
        q, k, v, causal=causal, window=cfg.sliding_window,
        q_chunk=cfg.attn_q_chunk, kv_chunk=cfg.attn_kv_chunk,
        dense_below=cfg.attn_dense_below, backend=cfg.gemm_backend,
        interpret=cfg.pallas_interpret)
    B, S = x.shape[0], x.shape[1]
    out = layers.linear(p["wo"], out.reshape(B, S, -1), cd,
                        site="xattn.wo" if kv_src is not None else "attn.wo",
                        backend=cfg.gemm_backend,
                        interpret=cfg.pallas_interpret)
    return out, (k, v)


def attn_decode(p, x, cfg: ModelConfig, cache, pos, norm_scale=None):
    """Single-token attention.  x: (B,1,d); cache: {'k','v'} ring buffers.

    pos may be a scalar (fused fleet decode; cheap dynamic-update-slice) or
    a (B,) vector (ragged continuous batching; masked per-row write).
    """
    cd = _cdtype(cfg)
    q, k_new, v_new = _proj_qkv(p, x, None, cfg, cd, norm_scale)
    B = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    positions = jnp.broadcast_to(pos, (B,))[:, None]
    q = layers.apply_rope(q, positions, cfg.rope_theta)
    k_new = layers.apply_rope(k_new, positions, cfg.rope_theta)
    cl = cache["k"].shape[1]
    if pos.ndim == 0:
        slot = (pos % cl) if cfg.sliding_window else jnp.minimum(pos, cl - 1)
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k_new.astype(cache["k"].dtype), slot, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v_new.astype(cache["v"].dtype), slot, axis=1)
    else:
        slot = (pos % cl) if cfg.sliding_window else jnp.minimum(pos, cl - 1)
        hit = (jnp.arange(cl)[None, :] == slot[:, None])[:, :, None, None]
        k_cache = jnp.where(hit, k_new.astype(cache["k"].dtype), cache["k"])
        v_cache = jnp.where(hit, v_new.astype(cache["v"].dtype), cache["v"])
    out = attn_lib.decode_attention(q, k_cache, v_cache, pos,
                                    window=cfg.sliding_window,
                                    backend=cfg.gemm_backend,
                                    interpret=cfg.pallas_interpret)
    out = layers.linear(p["wo"], out.reshape(B, 1, -1), cd, site="attn.wo",
                        backend=cfg.gemm_backend,
                        interpret=cfg.pallas_interpret)
    return out, {"k": k_cache, "v": v_cache}


def attn_prefill(p, x, cfg: ModelConfig, cache, pos, lengths,
                 norm_scale=None):
    """Chunked-prefill attention.  x: (B,C,d) — a chunk of C prompt tokens
    per row starting at absolute position ``pos`` (B,); ``lengths`` (B,) is
    the number of valid tokens in each row's chunk (0 = row not prefilled
    this call: its cache bits are left untouched).

    K/V for the valid (row, position) pairs are written into the cache by a
    masked gather-select (no arithmetic on cache values), then every query
    attends over the full cache buffer with a ``key_pos <= q_pos`` mask.
    The numerics deliberately mirror ``attn_decode``/``decode_attention``
    step for step — same cache-dtype readback, same fp32 score/softmax,
    same einsum contractions — so a chunked prefill reproduces the
    token-by-token decode path bit for bit.
    """
    cd = _cdtype(cfg)
    q, k_new, v_new = _proj_qkv(p, x, None, cfg, cd, norm_scale)
    B, C = x.shape[0], x.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    positions = pos[:, None] + jnp.arange(C)[None, :]          # (B,C)
    q = layers.apply_rope(q, positions, cfg.rope_theta)
    k_new = layers.apply_rope(k_new, positions, cfg.rope_theta)
    cl = cache["k"].shape[1]
    # masked scatter: cache slot j takes chunk element j - pos[b] when that
    # index is a valid token of this chunk, else keeps its current value.
    j = jnp.arange(cl)[None, :]                                # (1,cl)
    src = j - pos[:, None]                                     # (B,cl)
    ok = (src >= 0) & (src < lengths[:, None])
    idx = jnp.clip(src, 0, C - 1)[:, :, None, None]
    k_cache = jnp.where(
        ok[:, :, None, None],
        jnp.take_along_axis(k_new.astype(cache["k"].dtype), idx, axis=1),
        cache["k"])
    v_cache = jnp.where(
        ok[:, :, None, None],
        jnp.take_along_axis(v_new.astype(cache["v"].dtype), idx, axis=1),
        cache["v"])
    # causal attention of the C queries against the full (masked) buffer;
    # QK/PV dispatch through the substrate (attn.qk / attn.pv) exactly as
    # attn_lib.decode_attention does, preserving bit-for-bit prefill/decode
    # equivalence
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    g = H // KV
    qg = q.reshape(B, C, KV, g, hd)
    scale = 1.0 / math.sqrt(hd)
    s = attn_lib.qk_scores(qg, k_cache, backend=cfg.gemm_backend,
                           interpret=cfg.pallas_interpret) * scale
    valid = j[:, None, :] <= positions[:, :, None]             # (B,C,cl)
    s = jnp.where(valid[:, None, None], s, attn_lib.NEG_INF)
    w = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(v_cache.dtype)
    out = attn_lib.pv_mix(w, v_cache, backend=cfg.gemm_backend,
                          interpret=cfg.pallas_interpret)
    out = out.reshape(B, C, H, hd).astype(q.dtype)
    out = layers.linear(p["wo"], out.reshape(B, C, -1), cd, site="attn.wo",
                        backend=cfg.gemm_backend,
                        interpret=cfg.pallas_interpret)
    return out, {"k": k_cache, "v": v_cache}


def attn_decode_paged(p, x, cfg: ModelConfig, cache, layer, pos,
                      block_tables, norm_scale=None):
    """Single-token attention against the paged K/V pool.

    cache: {'kp','vp'} stacked physical pools (n_layers, n_pages, page,
    KV*hd) (:func:`init_paged_cache`), read at ``layer``; block_tables:
    (B, n_pg) int32 physical page ids per sequence.  The pool is gathered
    into the (B, L = n_pg*page, KV, hd) logical view — L equals the dense
    cache length by the engine's page|max_seq contract — then the write,
    mask, softmax and QK/PV dispatches are *identical* to the dense
    ``attn_decode`` vector-pos path, which is what keeps paged and dense
    greedy streams bit-identical.  The pool itself is not written: returns
    the attention output and the token's new rows ``{'kp','vp'}`` (B,
    KV*hd), which :func:`_paged_step` writes to row ``slot % page`` of
    each row's physical page after the layer scan.
    """
    cd = _cdtype(cfg)
    q, k_new, v_new = _proj_qkv(p, x, None, cfg, cd, norm_scale)
    B = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    positions = jnp.broadcast_to(pos, (B,))[:, None]
    q = layers.apply_rope(q, positions, cfg.rope_theta)
    k_new = layers.apply_rope(k_new, positions, cfg.rope_theta)
    k_new = k_new.astype(cache["kp"].dtype)
    v_new = v_new.astype(cache["vp"].dtype)
    bt = jnp.asarray(block_tables, jnp.int32)
    KV = cfg.n_kv_heads
    k_view = attn_lib.gather_pages(cache["kp"], layer, bt, KV)
    v_view = attn_lib.gather_pages(cache["vp"], layer, bt, KV)
    L = k_view.shape[1]
    slot = jnp.minimum(jnp.broadcast_to(pos, (B,)), L - 1)
    hit = (jnp.arange(L)[None, :] == slot[:, None])[:, :, None, None]
    k_view = jnp.where(hit, k_new, k_view)
    v_view = jnp.where(hit, v_new, v_view)
    out = attn_lib.decode_attention(q, k_view, v_view, pos, window=0,
                                    backend=cfg.gemm_backend,
                                    interpret=cfg.pallas_interpret)
    out = layers.linear(p["wo"], out.reshape(B, 1, -1), cd, site="attn.wo",
                        backend=cfg.gemm_backend,
                        interpret=cfg.pallas_interpret)
    return out, {"kp": k_new.reshape(B, -1), "vp": v_new.reshape(B, -1)}


def attn_prefill_paged(p, x, cfg: ModelConfig, cache, layer, pos, lengths,
                       block_tables, norm_scale=None):
    """Chunked-prefill attention against the paged K/V pool.

    The logical view is gathered exactly as in :func:`attn_decode_paged`;
    the masked chunk scatter, causal mask and QK/PV dispatches then
    mirror the dense ``attn_prefill`` step for step.  The pool is not
    written: returns the attention output and the chunk's new rows
    ``{'kp','vp'}`` (B, C, KV*hd), of which :func:`_paged_step` writes
    positions ``pos + c`` for ``c < lengths`` after the layer scan.
    """
    cd = _cdtype(cfg)
    q, k_new, v_new = _proj_qkv(p, x, None, cfg, cd, norm_scale)
    B, C = x.shape[0], x.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    positions = pos[:, None] + jnp.arange(C)[None, :]          # (B,C)
    q = layers.apply_rope(q, positions, cfg.rope_theta)
    k_new = layers.apply_rope(k_new, positions, cfg.rope_theta)
    k_new = k_new.astype(cache["kp"].dtype)
    v_new = v_new.astype(cache["vp"].dtype)
    bt = jnp.asarray(block_tables, jnp.int32)
    KV = cfg.n_kv_heads
    k_view = attn_lib.gather_pages(cache["kp"], layer, bt, KV)
    v_view = attn_lib.gather_pages(cache["vp"], layer, bt, KV)
    L = k_view.shape[1]
    j = jnp.arange(L)[None, :]                                 # (1,L)
    src = j - pos[:, None]                                     # (B,L)
    ok = (src >= 0) & (src < lengths[:, None])
    idx = jnp.clip(src, 0, C - 1)[:, :, None, None]
    k_view = jnp.where(ok[:, :, None, None],
                       jnp.take_along_axis(k_new, idx, axis=1), k_view)
    v_view = jnp.where(ok[:, :, None, None],
                       jnp.take_along_axis(v_new, idx, axis=1), v_view)
    H, hd = cfg.n_heads, cfg.resolved_head_dim
    g = H // KV
    qg = q.reshape(B, C, KV, g, hd)
    scale = 1.0 / math.sqrt(hd)
    s = attn_lib.qk_scores(qg, k_view, backend=cfg.gemm_backend,
                           interpret=cfg.pallas_interpret) * scale
    valid = j[:, None, :] <= positions[:, :, None]             # (B,C,L)
    s = jnp.where(valid[:, None, None], s, attn_lib.NEG_INF)
    w = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(v_view.dtype)
    out = attn_lib.pv_mix(w, v_view, backend=cfg.gemm_backend,
                          interpret=cfg.pallas_interpret)
    out = out.reshape(B, C, H, hd).astype(q.dtype)
    out = layers.linear(p["wo"], out.reshape(B, C, -1), cd, site="attn.wo",
                        backend=cfg.gemm_backend,
                        interpret=cfg.pallas_interpret)
    return out, {"kp": k_new.reshape(B, C, -1),
                 "vp": v_new.reshape(B, C, -1)}


def cross_attn_decode(p, x, cfg: ModelConfig, cache):
    """Cross-attention against precomputed (xk, xv)."""
    cd = _cdtype(cfg)
    B = x.shape[0]
    hd, H = cfg.resolved_head_dim, cfg.n_heads
    q = layers.linear(p["wq"], x, cd, site="xattn.wq",
                      backend=cfg.gemm_backend,
                      interpret=cfg.pallas_interpret).reshape(B, 1, H, hd)
    out = attn_lib.dense_attention(q, cache["xk"].astype(cd),
                                   cache["xv"].astype(cd), causal=False,
                                   backend=cfg.gemm_backend,
                                   interpret=cfg.pallas_interpret)
    return layers.linear(p["wo"], out.reshape(B, 1, -1), cd, site="xattn.wo",
                         backend=cfg.gemm_backend,
                         interpret=cfg.pallas_interpret)


# ---------------------------------------------------------------------------
# sub-layer (one transformer/mamba layer)

def sublayer_init(key, cfg: ModelConfig, pos: int):
    kind = sublayer_kind(cfg, pos)
    dtype = _pdtype(cfg)
    d = cfg.d_model
    ks = jax.random.split(key, 6)
    p = {"ln1": layers.rmsnorm_init(d, dtype)}
    if kind["mixer"] == "attn":
        p["attn"] = attn_init(ks[0], cfg, dtype)
    else:
        p["mamba"] = mamba_lib.mamba_init(ks[0], d, cfg.ssm or SSMConfig(),
                                          dtype)
    if kind["cross"]:
        p["lnx"] = layers.rmsnorm_init(d, dtype)
        p["xattn"] = attn_init(ks[1], cfg, dtype)
    if kind["mlp"] == "dense":
        p["ln2"] = layers.rmsnorm_init(d, dtype)
        p["mlp"] = layers.swiglu_init(ks[2], d, cfg.d_ff, dtype)
    elif kind["mlp"] == "moe":
        m = cfg.moe
        p["ln2"] = layers.rmsnorm_init(d, dtype)
        p["moe"] = moe_lib.moe_init(ks[3], d, m.expert_d_ff or cfg.d_ff,
                                    m.num_experts,
                                    num_shared=m.num_shared_experts,
                                    held=m.experts_held, dtype=dtype)
    return p


def sublayer_full(p, cfg: ModelConfig, pos: int, x, aux, positions, ctx):
    """Full-sequence sub-layer.  Returns (x, aux, cache_entry)."""
    kind = sublayer_kind(cfg, pos)
    cache = {}
    if kind["mixer"] == "attn":
        # ln1 scale fuses into the q/k/v projection prologues
        h = layers.rmsnorm_normalize(x, cfg.rms_eps)
        out, (k, v) = attn_full(p["attn"], h, cfg, positions,
                                norm_scale=p["ln1"]["scale"])
        cl = cache_len(cfg, k.shape[1])
        S = k.shape[1]
        k_c, v_c = k[:, S - cl:], v[:, S - cl:]
        if cfg.sliding_window and cl > 1:
            shift = S % cl
            k_c = jnp.roll(k_c, shift, axis=1)
            v_c = jnp.roll(v_c, shift, axis=1)
        cache = {"k": k_c.astype(jnp.bfloat16), "v": v_c.astype(jnp.bfloat16)}
    else:
        h = layers.rmsnorm(p["ln1"], x, cfg.rms_eps)
        out, state, conv = mamba_lib.mamba_forward(
            p["mamba"], h, cfg.ssm or SSMConfig(), _cdtype(cfg),
            backend=cfg.gemm_backend,
            interpret=cfg.pallas_interpret)
        cache = {"state": state.astype(jnp.float32),
                 "conv": conv.astype(jnp.bfloat16)}
    x = x + out
    if kind["cross"]:
        h = layers.rmsnorm(p["lnx"], x, cfg.rms_eps)
        out, (xk, xv) = attn_full(p["xattn"], h, cfg, positions,
                                  causal=False, kv_src=ctx)
        cache["xk"] = xk.astype(jnp.bfloat16)
        cache["xv"] = xv.astype(jnp.bfloat16)
        x = x + out
    if kind["mlp"] == "dense":
        # ln2 scale fuses into the dual-GEMM swiglu prologue; the
        # residual join fuses into the mlp.wo store
        h = layers.rmsnorm_normalize(x, cfg.rms_eps)
        x = layers.swiglu(p["mlp"], h, _cdtype(cfg),
                          backend=cfg.gemm_backend,
                          interpret=cfg.pallas_interpret, residual=x,
                          norm_scale=p["ln2"]["scale"])
    elif kind["mlp"] == "moe":
        h = layers.rmsnorm(p["ln2"], x, cfg.rms_eps)
        m = cfg.moe
        y, a = moe_lib.moe_apply(p["moe"], h, top_k=m.top_k,
                                 capacity_factor=m.capacity_factor,
                                 groups=0,  # one dispatch group per sequence
                                 compute_dtype=_cdtype(cfg),
                                 aux_loss_weight=m.aux_loss_weight,
                                 backend=cfg.gemm_backend,
                                 interpret=cfg.pallas_interpret)
        x = x + y
        aux = aux + a
    return x, aux, cache


def sublayer_decode(p, cfg: ModelConfig, pos_idx: int, x, cache, pos, ctx):
    """One-token sub-layer.  x: (B,1,d).  Returns (x, new_cache)."""
    kind = sublayer_kind(cfg, pos_idx)
    new_cache = dict(cache)
    if kind["mixer"] == "attn":
        # ln1 scale fuses into the q/k/v projection prologues
        h = layers.rmsnorm_normalize(x, cfg.rms_eps)
        out, kv = attn_decode(p["attn"], h, cfg, cache, pos,
                              norm_scale=p["ln1"]["scale"])
        new_cache.update(kv)
    else:
        h = layers.rmsnorm(p["ln1"], x, cfg.rms_eps)
        out, state, conv = mamba_lib.mamba_decode_step(
            p["mamba"], h[:, 0], cache["state"], cache["conv"],
            cfg.ssm or SSMConfig(), _cdtype(cfg),
            backend=cfg.gemm_backend,
            interpret=cfg.pallas_interpret)
        out = out[:, None]
        new_cache["state"] = state
        new_cache["conv"] = conv.astype(cache["conv"].dtype)
    x = x + out
    if kind["cross"]:
        h = layers.rmsnorm(p["lnx"], x, cfg.rms_eps)
        x = x + cross_attn_decode(p["xattn"], h, cfg, cache)
    x, _ = _mlp_serve(p, cfg, kind, x)
    return x, new_cache


def _mlp_serve(p, cfg: ModelConfig, kind: dict, x):
    """A serving sub-layer's MLP on the residual stream x (B,S,d).
    Returns (x, routed): ``routed`` (B,S) counts each token's (token, held
    expert) pairs in an expert layer, else None.  Expert layers route
    dropless and per token (``moe.moe_serve``), so every serving path
    gives a token the same output whatever else shares its dispatch."""
    if kind["mlp"] == "dense":
        # ln2 scale fuses into the dual-GEMM swiglu prologue; the
        # residual join fuses into the mlp.wo store
        h = layers.rmsnorm_normalize(x, cfg.rms_eps)
        return layers.swiglu(p["mlp"], h, _cdtype(cfg),
                             backend=cfg.gemm_backend,
                             interpret=cfg.pallas_interpret, residual=x,
                             norm_scale=p["ln2"]["scale"]), None
    if kind["mlp"] == "moe":
        h = layers.rmsnorm(p["ln2"], x, cfg.rms_eps)
        y, routed = moe_lib.moe_serve(p["moe"], h, top_k=cfg.moe.top_k,
                                      first=cfg.moe.expert_first,
                                      compute_dtype=_cdtype(cfg),
                                      backend=cfg.gemm_backend,
                                      interpret=cfg.pallas_interpret)
        return x + y, routed
    return x, None


def sublayer_prefill(p, cfg: ModelConfig, pos_idx: int, x, cache, pos,
                     lengths):
    """Chunk-of-tokens sub-layer.  x: (B,C,d).  Returns (x, new_cache).

    Attention-mixer sub-layers only (``supports_batched_prefill`` gates the
    callers); the residual/MLP arithmetic is row-wise identical to
    ``sublayer_decode``.
    """
    kind = sublayer_kind(cfg, pos_idx)
    assert kind["mixer"] == "attn" and not kind["cross"], \
        "use supports_batched_prefill() to gate"
    new_cache = dict(cache)
    h = layers.rmsnorm_normalize(x, cfg.rms_eps)
    out, kv = attn_prefill(p["attn"], h, cfg, cache, pos, lengths,
                           norm_scale=p["ln1"]["scale"])
    new_cache.update(kv)
    x, _ = _mlp_serve(p, cfg, kind, x + out)
    return x, new_cache


# ---------------------------------------------------------------------------
# whole model

def _stacked_init(key, n: int, fn):
    keys = jax.random.split(key, n)
    return jax.vmap(fn)(keys)


def init_params(cfg: ModelConfig, key):
    P = period(cfg)
    NS = n_super(cfg)
    dtype = _pdtype(cfg)
    keys = jax.random.split(key, P + 6)
    params = {
        "embed": layers.embedding_init(keys[-1], cfg.padded_vocab,
                                       cfg.d_model, dtype),
        "final_norm": layers.rmsnorm_init(cfg.d_model, dtype),
        "blocks": tuple(
            _stacked_init(keys[i], NS,
                          partial(sublayer_init, cfg=cfg, pos=i))
            for i in range(P)),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = layers.linear_init(keys[-2], cfg.d_model,
                                               cfg.padded_vocab, dtype=dtype)
    if cfg.family == "vlm":
        params["img_proj"] = layers.linear_init(keys[-3], cfg.d_frontend,
                                                cfg.d_model, dtype=dtype)
    if cfg.family == "audio":
        params["audio_proj"] = layers.linear_init(keys[-4], cfg.d_frontend,
                                                  cfg.d_model, dtype=dtype)
        params["enc_blocks"] = (_stacked_init(
            keys[-5], cfg.n_encoder_layers,
            partial(_enc_layer_init, cfg=cfg)),)
    return params


def _enc_layer_init(key, cfg: ModelConfig):
    dtype = _pdtype(cfg)
    ks = jax.random.split(key, 2)
    return {"ln1": layers.rmsnorm_init(cfg.d_model, dtype),
            "attn": attn_init(ks[0], cfg, dtype),
            "ln2": layers.rmsnorm_init(cfg.d_model, dtype),
            "mlp": layers.swiglu_init(ks[1], cfg.d_model, cfg.d_ff, dtype)}


def _remat(cfg, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        pol = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(fn, policy=pol)
    return jax.checkpoint(fn)


def _encode_audio(cfg, params, frames):
    cd = _cdtype(cfg)
    x = layers.linear(params["audio_proj"], frames, cd,
                      site="frontend.audio", backend=cfg.gemm_backend,
                                             interpret=cfg.pallas_interpret)
    positions = jnp.arange(x.shape[1])[None, :]

    def body(carry, p):
        x = carry
        h = layers.rmsnorm_normalize(x, cfg.rms_eps)
        out, _ = attn_full(p["attn"], h, cfg, positions, causal=False,
                           norm_scale=p["ln1"]["scale"])
        x = x + out
        h = layers.rmsnorm_normalize(x, cfg.rms_eps)
        x = layers.swiglu(p["mlp"], h, cd, backend=cfg.gemm_backend,
                          interpret=cfg.pallas_interpret, residual=x,
                          norm_scale=p["ln2"]["scale"])
        return x, None

    x, _ = jax.lax.scan(_remat(cfg, body), x, params["enc_blocks"][0])
    return x


def _logits(cfg, params, x, cd):
    """fp32 logits via the substrate (site "unembed", tied or untied)."""
    if cfg.tie_embeddings:
        return layers.unembed(params["embed"], x, backend=cfg.gemm_backend,
                                                  interpret=cfg.pallas_interpret)
    return layers.linear(params["lm_head"], x, cd, site="unembed",
                         backend=cfg.gemm_backend,
                         interpret=cfg.pallas_interpret).astype(jnp.float32)


def _context(cfg, params, batch):
    if cfg.family == "vlm":
        return layers.linear(params["img_proj"],
                             batch["image_embeds"].astype(_cdtype(cfg)),
                             _cdtype(cfg), site="frontend.img",
                             backend=cfg.gemm_backend,
                             interpret=cfg.pallas_interpret)
    if cfg.family == "audio":
        return _encode_audio(cfg, params, batch["frames"])
    return None


def forward(cfg: ModelConfig, params, batch, *, return_cache=False):
    """Returns (logits, aux_loss, cache-or-None).  batch['tokens']: (B,S).

    Activates cfg's GEMM-dispatch mesh (``mesh_shape``) for the trace, so
    every substrate dispatch below derives its per-site shard context and
    the planner sees post-partition shapes.
    """
    substrate.check_backend(cfg.gemm_backend)
    with sharding.gemm_mesh_scope(cfg):
        return _forward(cfg, params, batch, return_cache=return_cache)


def _forward(cfg: ModelConfig, params, batch, *, return_cache=False):
    P = period(cfg)
    cd = _cdtype(cfg)
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = constrain(layers.embed(params["embed"], tokens, cd), "hidden")
    positions = jnp.arange(S)[None, :]
    ctx = _context(cfg, params, batch)

    def body(carry, p_block):
        x, aux = carry
        caches = []
        for i in range(P):
            x, aux, c = sublayer_full(p_block[i], cfg, i, x, aux,
                                      positions, ctx)
            x = constrain(x, "hidden")
            caches.append(c)
        return (x, aux), tuple(caches) if return_cache else None

    (x, aux), caches = jax.lax.scan(_remat(cfg, body), (x, jnp.float32(0.0)),
                                    params["blocks"])
    x = layers.rmsnorm(params["final_norm"], x, cfg.rms_eps)
    logits = _logits(cfg, params, x, cd)
    return constrain(logits, "logits"), aux, caches


def loss_fn(cfg: ModelConfig, params, batch):
    logits, aux, _ = forward(cfg, params, batch)
    loss = layers.softmax_xent(logits, batch["labels"],
                               batch.get("loss_mask"))
    return loss + aux, {"xent": loss, "aux": aux}


def prefill(cfg: ModelConfig, params, batch):
    """Returns (last-token logits (B,V), cache pytree)."""
    logits, _, caches = forward(cfg, params, batch, return_cache=True)
    return logits[:, -1], caches


def decode_step(cfg: ModelConfig, params, cache, token, pos, ctx=None):
    """token: (B,) int32; pos: scalar int32.  Returns (logits (B,V), cache).

    Activates cfg's GEMM-dispatch mesh (``mesh_shape``), like ``forward``.
    """
    substrate.check_backend(cfg.gemm_backend)
    with sharding.gemm_mesh_scope(cfg):
        return _decode_step(cfg, params, cache, token, pos, ctx)


def _decode_step(cfg: ModelConfig, params, cache, token, pos, ctx=None):
    P = period(cfg)
    cd = _cdtype(cfg)
    x = layers.embed(params["embed"], token[:, None], cd)

    def body(x, xs):
        p_block, cache_block = xs
        new_caches = []
        for i in range(P):
            x, nc = sublayer_decode(p_block[i], cfg, i, x, cache_block[i],
                                    pos, ctx)
            new_caches.append(nc)
        return x, tuple(new_caches)

    x, new_cache = jax.lax.scan(body, x, (params["blocks"], cache))
    x = layers.rmsnorm(params["final_norm"], x, cfg.rms_eps)
    logits = _logits(cfg, params, x, cd)
    return constrain(logits, "logits")[:, 0], new_cache


def supports_batched_prefill(cfg: ModelConfig) -> bool:
    """True when ``prefill_step`` reproduces the decode path bit-for-bit.

    Requires every sub-layer to be a causal-attention block with a linear
    (non-ring) KV cache and a dense or expert MLP: mamba state recurrences,
    cross-attention contexts and sliding-window ring buffers break per-row
    equivalence with single-token decoding.  Expert layers qualify because
    serving routes them dropless and per token (``moe.moe_serve``): a
    token's output does not depend on what else shares its dispatch.
    """
    if cfg.sliding_window or cfg.family in ("vlm", "audio"):
        return False
    return all(
        k["mixer"] == "attn" and not k["cross"]
        for k in (sublayer_kind(cfg, i) for i in range(period(cfg))))


def routes_experts(cfg: ModelConfig) -> bool:
    """True when some sub-layer is an expert layer."""
    return any(sublayer_kind(cfg, i)["mlp"] == "moe"
               for i in range(period(cfg)))


def prefill_step(cfg: ModelConfig, params, cache, tokens, pos, lengths):
    """Batched chunked prefill: one jit dispatch for a (B,C) token chunk.

    tokens: (B,C) int32, right-padded; pos: (B,) absolute start position of
    each row's chunk; lengths: (B,) valid tokens per row (0 = row inactive —
    its cache is untouched, fixing the garbage K/V writes the per-token
    prefill path inflicted on co-resident slots).  Returns
    ``(logits (B,V) at each row's last valid chunk token, new_cache)``;
    logits rows with ``lengths == 0`` are meaningless.

    Activates cfg's GEMM-dispatch mesh (``mesh_shape``), like ``forward``.
    """
    substrate.check_backend(cfg.gemm_backend)
    with sharding.gemm_mesh_scope(cfg):
        return _prefill_step(cfg, params, cache, tokens, pos, lengths)


def _prefill_step(cfg: ModelConfig, params, cache, tokens, pos, lengths):
    P = period(cfg)
    cd = _cdtype(cfg)
    tokens = jnp.asarray(tokens, jnp.int32)
    C = tokens.shape[1]
    x = layers.embed(params["embed"], tokens, cd)

    def body(x, xs):
        p_block, cache_block = xs
        new_caches = []
        for i in range(P):
            x, nc = sublayer_prefill(p_block[i], cfg, i, x, cache_block[i],
                                     pos, lengths)
            new_caches.append(nc)
        return x, tuple(new_caches)

    x, new_cache = jax.lax.scan(body, x, (params["blocks"], cache))
    x = layers.rmsnorm(params["final_norm"], x, cfg.rms_eps)
    last = jnp.clip(jnp.asarray(lengths, jnp.int32) - 1, 0, C - 1)
    x = jnp.take_along_axis(x, last[:, None, None], axis=1)    # (B,1,d)
    logits = _logits(cfg, params, x, cd)
    return constrain(logits, "logits")[:, 0], new_cache


# ---------------------------------------------------------------------------
# pipeline-sharded serving steps (GPipe stages over the 'pod' mesh axis)

def supports_pipeline(cfg: ModelConfig) -> bool:
    """True when the pp step functions reproduce the dense path bit for
    bit: plain causal-attention + dense-MLP stack (the batched-prefill
    gate, expert layers not staged) whose ``n_super`` super-blocks split
    evenly over the stages."""
    pp = cfg.pp_stages
    return (pp > 1 and len(cfg.mesh_shape) == 3
            and cfg.mesh_shape[0] == pp and n_super(cfg) % pp == 0
            and supports_batched_prefill(cfg) and not routes_experts(cfg))


def _check_pp(cfg: ModelConfig):
    if not supports_pipeline(cfg):
        raise ValueError(
            "pipeline step needs pp_stages > 1, a 3-axis mesh_shape whose "
            "'pod' axis equals pp_stages, n_super %% pp == 0 and a "
            "batched-prefill-capable (dense causal) architecture; got "
            f"pp_stages={cfg.pp_stages} mesh_shape={cfg.mesh_shape} "
            f"n_super={n_super(cfg)} family={cfg.family}")


def _pp_step(cfg: ModelConfig, params, cache, tokens, pos, lengths):
    """Shared driver for the pipeline-sharded decode/prefill step.

    The whole step runs as ONE ``shard_map`` over cfg's (pod, data, model)
    mesh: ``params['blocks']`` and the dense KV cache shard their leading
    ``n_super`` dim over 'pod' (stage s owns the contiguous super-blocks
    ``[s*NS/pp, (s+1)*NS/pp)``), the embedded chunk enters stage 0, and
    ``parallel.pipeline.staged_step`` clocks it through the stages via
    ``collective_permute``.  Each stage scans its local super-blocks with
    the SAME sublayer functions as the colocated path, so the math is
    bit-identical; only the ``attn.wq`` boundary GEMM plans under the
    active role's transfer pricing (sharding.use_pp_pricing), which is how
    prefill pods and decode pods legitimately hold different ``best_k``.
    """
    from jax.sharding import PartitionSpec as P

    from repro.parallel import pipeline as pipe

    Pd = period(cfg)
    cd = _cdtype(cfg)
    mesh = sharding.mesh_from_config(cfg)
    decode = lengths is None
    other = {k: v for k, v in params.items() if k != "blocks"}

    def body(blocks, cache_l, other, tokens, pos, lengths):
        x0 = layers.embed(other["embed"], tokens, cd)

        def stage_fn(x, cache_c):
            def scan_body(x, xs):
                p_block, cache_block = xs
                ncs = []
                for i in range(Pd):
                    if decode:
                        x, nc = sublayer_decode(p_block[i], cfg, i, x,
                                                cache_block[i], pos, None)
                    else:
                        x, nc = sublayer_prefill(p_block[i], cfg, i, x,
                                                 cache_block[i], pos,
                                                 lengths)
                    ncs.append(nc)
                return x, tuple(ncs)
            return jax.lax.scan(scan_body, x, (blocks, cache_c))

        y, new_cache = pipe.staged_step(stage_fn, x0, cache_l,
                                        axis_name="pod")
        x = layers.rmsnorm(other["final_norm"], y, cfg.rms_eps)
        if not decode:
            C = tokens.shape[1]
            last = jnp.clip(jnp.asarray(lengths, jnp.int32) - 1, 0, C - 1)
            x = jnp.take_along_axis(x, last[:, None, None], axis=1)
        logits = _logits(cfg, other, x, cd)
        # only the last stage holds real logits; mask + psum broadcasts
        stage = jax.lax.axis_index("pod")
        n_stages = jax.lax.psum(1, "pod")
        logits = jax.lax.psum(
            logits * (stage == n_stages - 1).astype(logits.dtype), "pod")
        return logits[:, 0], new_cache

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P("pod"), P("pod"), P(), P(), P(), P()),
                       out_specs=(P(), P("pod")), check_vma=False)
    return fn(params["blocks"], cache, other, tokens, pos, lengths)


def decode_step_pp(cfg: ModelConfig, params, cache, token, pos):
    """Pipeline-sharded twin of :func:`decode_step` (dense cache only).

    token: (B,) int32; pos: scalar or (B,) int32.  Returns
    (logits (B,V), new_cache) bit-identical to :func:`decode_step`."""
    substrate.check_backend(cfg.gemm_backend)
    _check_pp(cfg)
    with sharding.gemm_mesh_scope(cfg):
        return _pp_step(cfg, params, cache, token[:, None], pos, None)


def prefill_step_pp(cfg: ModelConfig, params, cache, tokens, pos, lengths):
    """Pipeline-sharded twin of :func:`prefill_step` (dense cache only)."""
    substrate.check_backend(cfg.gemm_backend)
    _check_pp(cfg)
    with sharding.gemm_mesh_scope(cfg):
        return _pp_step(cfg, params, cache, jnp.asarray(tokens, jnp.int32),
                        pos, lengths)


def supports_paged_kv(cfg: ModelConfig) -> bool:
    """True when the paged serving path reproduces dense decoding bit for
    bit: same gate as :func:`supports_batched_prefill` (causal attn + a
    dense or per-token expert MLP, linear cache) — mamba state,
    cross-attention and sliding-window rings have no page-gather
    equivalence."""
    return supports_batched_prefill(cfg)


def _sublayer_decode_paged(p, cfg, pos_idx, x, cache, layer, pos, bt):
    kind = sublayer_kind(cfg, pos_idx)
    assert kind["mixer"] == "attn" and not kind["cross"], \
        "use supports_paged_kv() to gate"
    h = layers.rmsnorm_normalize(x, cfg.rms_eps)
    out, rows = attn_decode_paged(p["attn"], h, cfg, cache, layer, pos, bt,
                                  norm_scale=p["ln1"]["scale"])
    return (*_mlp_serve(p, cfg, kind, x + out), rows)


def _sublayer_prefill_paged(p, cfg, pos_idx, x, cache, layer, pos, lengths,
                            bt):
    kind = sublayer_kind(cfg, pos_idx)
    assert kind["mixer"] == "attn" and not kind["cross"], \
        "use supports_paged_kv() to gate"
    h = layers.rmsnorm_normalize(x, cfg.rms_eps)
    out, rows = attn_prefill_paged(p["attn"], h, cfg, cache, layer, pos,
                                   lengths, bt, norm_scale=p["ln1"]["scale"])
    return (*_mlp_serve(p, cfg, kind, x + out), rows)


def decode_step_paged(cfg: ModelConfig, params, cache, token, pos,
                      block_tables):
    """Paged twin of :func:`decode_step`: token (B,), pos (B,),
    block_tables (B, n_pg) int32.  cache is :func:`init_paged_cache`'s
    pytree.  Returns (logits (B,V), new_cache); a model with expert layers
    (:func:`routes_experts`) adds ``routed`` (B,) int32, each row's
    (token, held expert) pairs summed over the layers."""
    substrate.check_backend(cfg.gemm_backend)
    with sharding.gemm_mesh_scope(cfg):
        return _paged_step(cfg, params, cache, token[:, None], pos,
                           None, block_tables)


def prefill_step_paged(cfg: ModelConfig, params, cache, tokens, pos,
                       lengths, block_tables):
    """Paged twin of :func:`prefill_step`: tokens (B,C) right-padded,
    pos/lengths (B,), block_tables (B, n_pg).  Returns (logits at each
    row's last valid chunk token, new_cache), and for a model with expert
    layers ``routed`` (B,): each row's (token, held expert) pairs over its
    valid chunk tokens, summed over the layers."""
    substrate.check_backend(cfg.gemm_backend)
    with sharding.gemm_mesh_scope(cfg):
        return _paged_step(cfg, params, cache, tokens, pos, lengths,
                           block_tables)


def _paged_step(cfg, params, cache, tokens, pos, lengths, bt):
    """The paged steps' layer scan.  The scan runs over the parameters and
    the layer index only: each layer gathers its pages from the stacked
    pool where it lies, and returns its new K/V rows, not a pool.  After
    the scan one scatter per pool leaf writes every layer's rows, so a
    donated pool is updated in place (no pool-sized value is made)."""
    P = period(cfg)
    cd = _cdtype(cfg)
    tokens = jnp.asarray(tokens, jnp.int32)
    bt = jnp.asarray(bt, jnp.int32)
    C = tokens.shape[1]
    x = layers.embed(params["embed"], tokens, cd)

    def body(x, xs):
        p_block, layer = xs
        rows, routed = [], None
        for i in range(P):
            if lengths is None:
                x, r, kv = _sublayer_decode_paged(p_block[i], cfg, i, x,
                                                  cache[i], layer, pos, bt)
            else:
                x, r, kv = _sublayer_prefill_paged(p_block[i], cfg, i, x,
                                                   cache[i], layer, pos,
                                                   lengths, bt)
            rows.append(kv)
            if r is not None:
                routed = r if routed is None else routed + r
        return x, (tuple(rows), routed)

    layer_ids = jnp.arange(n_super(cfg), dtype=jnp.int32)
    x, (rows, routed) = jax.lax.scan(body, x, (params["blocks"], layer_ids))
    new_cache = _write_rows(cache, rows, pos, lengths, bt)
    x = layers.rmsnorm(params["final_norm"], x, cfg.rms_eps)
    if lengths is not None:
        lengths = jnp.asarray(lengths, jnp.int32)
        last = jnp.clip(lengths - 1, 0, C - 1)
        x = jnp.take_along_axis(x, last[:, None, None], axis=1)  # (B,1,d)
    logits = _logits(cfg, params, x, cd)
    out = (constrain(logits, "logits")[:, 0], new_cache)
    if routed is None:
        return out
    routed = jnp.sum(routed, axis=0)                             # (B,C)
    if lengths is not None:                      # valid chunk tokens only
        routed = jnp.where(jnp.arange(C)[None, :] < lengths[:, None],
                           routed, 0)
    return (*out, jnp.sum(routed, axis=1))


def _write_rows(cache, rows, pos, lengths, bt):
    """Write the layer scan's new K/V rows into the stacked pools: one
    scatter per pool leaf over (layer, physical page, row in page).

    Decode (``lengths`` None): rows (NS, B, KV*hd) go to row ``slot %
    page`` of page ``bt[b, slot // page]``, ``slot = min(pos, L - 1)`` as
    in the attention's view; an idle row (pos 0, table of zeros) writes
    the scratch page 0, which no live row attends.  Prefill: rows (NS, B,
    C, KV*hd) go to positions ``pos + c`` for ``c < lengths[b]``; padded
    chunk positions are dropped (an out-of-range page).  The engine's
    sharing invariant puts every write in a uniquely-owned page: shared
    prefix pages are never written, since writes start at the page-aligned
    divergence point."""
    n_pages, page = cache[0]["kp"].shape[1:3]
    B, n_pg = bt.shape
    L = n_pg * page
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    if lengths is None:
        slot = jnp.minimum(pos, L - 1)                              # (B,)
        phys = bt[jnp.arange(B), slot // page]
    else:
        j = jnp.arange(rows[0]["kp"].shape[2])[None, :]
        slot = pos[:, None] + j                                     # (B,C)
        keep = (j < jnp.asarray(lengths, jnp.int32)[:, None]) & (slot < L)
        phys = jnp.take_along_axis(bt, jnp.minimum(slot // page, n_pg - 1),
                                   axis=1)
        phys = jnp.where(keep, phys, n_pages)
    off = slot % page
    layer = jnp.arange(cache[0]["kp"].shape[0]).reshape(
        (-1,) + (1,) * phys.ndim)
    return tuple(
        {name: pool.at[layer, phys, off].set(new[name], mode="drop")
         for name, pool in pools.items()}
        for pools, new in zip(cache, rows))


# ---------------------------------------------------------------------------
# pre-quantized parameter trees (load-time weight quantization)

def prequantize_params(cfg: ModelConfig, params):
    """Quantize every GEMM weight leaf once, eagerly, at load time.

    Returns a param tree where each weight the quantizing backend would
    quantize in-trace is replaced by a :class:`substrate.QuantizedTensor`
    (int8 codes + fp32 per-output-channel scales).  The dispatch then
    consumes the codes directly — the AF008 in-trace requantize (XLA
    re-running abs/max/round per compiled step) disappears from the
    jaxpr, and the hot path never touches the fp32 master weights.

    Bitwise contract: quantization is applied to the *compute-dtype cast*
    of each weight — exactly the value ``layers.linear`` hands the
    dispatch — and ``_quantize`` is elementwise + an exact (max) reduction,
    so eager codes equal in-trace codes bit for bit and pre-quantized
    streams match in-trace-quantized streams exactly.

    Skipped leaves mirror the dispatch rules: ``moe.router`` weights
    (:data:`substrate.QUANT_EXEMPT_SITES` — routing must stay fp32),
    biases, norms, mamba conv/state tensors, and the embedding lookup
    table (tied embeddings get an extra pre-transposed ``table_q`` leaf
    that ``layers.unembed`` prefers).  No-op (returns ``params``
    unchanged) when ``cfg.gemm_backend`` does not quantize.
    """
    if not substrate.backend_quantizes(cfg.gemm_backend):
        return params
    cd = _cdtype(cfg)

    def q(w):
        return substrate.prequantize(w.astype(cd))

    def walk(node):
        if isinstance(node, dict):
            out = {}
            for k, v in node.items():
                if isinstance(v, (dict, tuple, list)):
                    out[k] = walk(v)
                elif k == "w" and getattr(v, "ndim", 0) >= 2:
                    out[k] = q(v)                      # linear weights
                elif (k in ("wi_gate", "wi_up", "wo")
                      and getattr(v, "ndim", 0) >= 3):
                    out[k] = q(v)                      # MoE expert banks
                else:
                    out[k] = v                         # router/bias/norm/...
            return out
        if isinstance(node, (tuple, list)):
            return type(node)(walk(v) for v in node)
        return node

    out = walk(params)
    if cfg.tie_embeddings:
        # unembed runs table.T as a GEMM weight: pre-transpose + quantize
        t = params["embed"]["table"].astype(cd)
        out["embed"] = dict(out["embed"],
                            table_q=substrate.prequantize(t.T))
    return out


# ---------------------------------------------------------------------------
# cache construction

def init_cache(cfg: ModelConfig, batch_size: int, max_seq: int,
               dtype=jnp.bfloat16):
    """Zero-initialized decode cache matching decode_step's expectations."""
    P = period(cfg)
    NS = n_super(cfg)
    ssm = cfg.ssm or SSMConfig()
    hd, KV = cfg.resolved_head_dim, cfg.n_kv_heads
    cl = cache_len(cfg, max_seq)
    d_in = cfg.d_inner
    G, N = ssm.n_groups, ssm.d_state
    hg = (d_in // ssm.head_dim) // G
    conv_ch = d_in + 2 * G * N
    out = []
    for i in range(P):
        kind = sublayer_kind(cfg, i)
        c = {}
        if kind["mixer"] == "attn":
            c["k"] = jnp.zeros((NS, batch_size, cl, KV, hd), dtype)
            c["v"] = jnp.zeros((NS, batch_size, cl, KV, hd), dtype)
        else:
            c["state"] = jnp.zeros((NS, batch_size, G, hg, ssm.head_dim, N),
                                   jnp.float32)
            c["conv"] = jnp.zeros((NS, batch_size, ssm.d_conv - 1, conv_ch),
                                  dtype)
        if kind["cross"]:
            xl = cross_len(cfg)
            c["xk"] = jnp.zeros((NS, batch_size, xl, KV, hd), dtype)
            c["xv"] = jnp.zeros((NS, batch_size, xl, KV, hd), dtype)
        out.append(c)
    return tuple(out)


def init_paged_cache(cfg: ModelConfig, n_pages: int, page_size: int,
                     dtype=jnp.bfloat16):
    """Zero-initialized paged K/V pools for ``decode_step_paged`` /
    ``prefill_step_paged``: per layer ``{'kp','vp'}`` of shape
    ``(NS, n_pages, page_size, KV * hd)``.  Unlike :func:`init_cache`
    there is no batch dimension — residency is the engine's block tables,
    so K/V memory scales with the page budget, not ``max_batch * max_seq``
    (page 0 is the engine's scratch page).  A token's heads lie side by
    side on one row, so a page is whole (page, KV*hd) tiles on a TPU and
    the device keeps the pool in its natural layout: with (KV, hd) as the
    minor axes a narrow head (qwen2-0.5b: 2 x 64) would make the device
    store pages along the lanes and re-lay the pool out on every step."""
    if not supports_paged_kv(cfg):
        raise ValueError(f"{cfg.name}: family does not support the paged "
                         f"KV path (see supports_paged_kv)")
    P = period(cfg)
    NS = n_super(cfg)
    hd, KV = cfg.resolved_head_dim, cfg.n_kv_heads
    return tuple(
        {"kp": jnp.zeros((NS, n_pages, page_size, KV * hd), dtype),
         "vp": jnp.zeros((NS, n_pages, page_size, KV * hd), dtype)}
        for _ in range(P))
