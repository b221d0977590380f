"""The Qwen2 decoder, as the benchmark sees it: its sizes, the program
settings they map to, its layer's seeded leaves, its plain float32 layer,
and the operations and bytes of its served work.

A configuration file names this module in its ``reference`` key; the
harness loads it by path and calls the names in ``harness.ARCH_NAMES``.
Nothing here imports the program.

The layer follows the published Qwen2 equations (Hugging Face
``modeling_qwen2``): RMSNorm, Q/K/V projections with bias, rotary position
embedding over rotate-half pairs, causal grouped-query attention with
1/sqrt(head_dim) scaling and a float32 softmax, output projection without
bias, residual, RMSNorm, SwiGLU MLP (silu(x Wg) * (x Wu)) Wd, residual.
Every product runs at ``Precision.HIGHEST`` (true float32 on a TPU).
Departures: none in the equations; the weights are the benchmark's seeded
random weights (``chipbench/weights.py``), and a sliced vocabulary is a
smaller vocabulary.

Every product of a Qwen2 step runs through one GEMM kernel per site: per
layer ``attn.wq``, ``attn.wk``, ``attn.wv``, ``attn.qk``, ``attn.pv``,
``attn.wo``, ``mlp.wi`` (gate and up in one dual call) and ``mlp.wo``, and
once per step ``unembed``.  A call's least time on the chip is the larger of
its operations over peak bf16 FLOP/s and its bytes over peak HBM bytes/s.
Shapes are logical: the rows the step dispatched, the unpadded contraction
and output widths, bf16 operands, and for ``qk`` and ``pv`` each row's live
context (keys actually attended), not the cache length.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.reference import HI, rmsnorm, rope
from chipbench.roofline import F32, gemm

# the source config.json's keys -> the program's ModelConfig fields
HF_TO_PROGRAM = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "rms_norm_eps": "rms_eps",
    "tie_word_embeddings": "tie_embeddings",
}


def _head_dim(conf: dict) -> int:
    """As stated, else hidden_size / num_attention_heads (Qwen2's
    config.json states none)."""
    return conf.get("head_dim",
                    conf["hidden_size"] // conf["num_attention_heads"])


def dims(conf: dict) -> dict:
    return dict(layers=conf["num_hidden_layers"], hidden=conf["hidden_size"],
                heads=conf["num_attention_heads"],
                kv_heads=conf["num_key_value_heads"],
                head_dim=_head_dim(conf),
                ffn=conf["intermediate_size"], vocab=conf["vocab_size"])


def program_kwargs(conf: dict) -> dict:
    """The program's ModelConfig fields this configuration states."""
    kw = {HF_TO_PROGRAM[k]: conf[k] for k in HF_TO_PROGRAM}
    kw.update(head_dim=_head_dim(conf), qkv_bias=conf["program"]["qkv_bias"],
              sliding_window=0)
    return kw


def reference_kwargs(conf: dict) -> dict:
    """``served_gaps``'s arguments: the final norm's eps, whether the head is
    the embedding, and ``layer``'s own keywords."""
    return dict(eps=conf["rms_norm_eps"], tied=conf["tie_word_embeddings"],
                theta=conf["rope_theta"])


# label -> shape from dims, of one layer's leaves; the label is also the
# leaf's path inside the program's stacked block
LAYER_LEAVES = {
    "ln1/scale": lambda d: (d["hidden"],),
    "attn/wq/w": lambda d: (d["hidden"], d["heads"] * d["head_dim"]),
    "attn/wq/b": lambda d: (d["heads"] * d["head_dim"],),
    "attn/wk/w": lambda d: (d["hidden"], d["kv_heads"] * d["head_dim"]),
    "attn/wk/b": lambda d: (d["kv_heads"] * d["head_dim"],),
    "attn/wv/w": lambda d: (d["hidden"], d["kv_heads"] * d["head_dim"]),
    "attn/wv/b": lambda d: (d["kv_heads"] * d["head_dim"],),
    "attn/wo/w": lambda d: (d["heads"] * d["head_dim"], d["hidden"]),
    "ln2/scale": lambda d: (d["hidden"],),
    "mlp/wi_gate/w": lambda d: (d["hidden"], d["ffn"]),
    "mlp/wi_up/w": lambda d: (d["hidden"], d["ffn"]),
    "mlp/wo/w": lambda d: (d["ffn"], d["hidden"]),
}


def layer(x, w, *, dims, eps, theta, q_block):
    """One decoder layer on x (N, L, d)."""
    N, L, d = x.shape
    H, KV, hd = dims["heads"], dims["kv_heads"], dims["head_dim"]
    g = H // KV

    def mm(a, b):
        return jnp.einsum("...k,kn->...n", a, b, precision=HI)

    h = rmsnorm(x, w["ln1/scale"], eps)
    q = (mm(h, w["attn/wq/w"]) + w["attn/wq/b"]).reshape(N, L, H, hd)
    k = (mm(h, w["attn/wk/w"]) + w["attn/wk/b"]).reshape(N, L, KV, hd)
    v = (mm(h, w["attn/wv/w"]) + w["attn/wv/b"]).reshape(N, L, KV, hd)
    q, k = rope(q, theta), rope(k, theta)
    q = q.reshape(N, L, KV, g, hd)
    outs = []
    for s in range(0, L, q_block):
        qb = q[:, s:s + q_block]
        sc = jnp.einsum("nqkgd,ntkd->nkgqt", qb, k, precision=HI)
        sc = sc / math.sqrt(hd)
        mask = (jnp.arange(L)[None, :]
                <= (s + jnp.arange(qb.shape[1]))[:, None])
        sc = jnp.where(mask, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("nkgqt,ntkd->nqkgd", p, v, precision=HI))
    o = jnp.concatenate(outs, axis=1).reshape(N, L, H * hd)
    x = x + mm(o, w["attn/wo/w"])
    h = rmsnorm(x, w["ln2/scale"], eps)
    a = jax.nn.silu(mm(h, w["mlp/wi_gate/w"])) * mm(h, w["mlp/wi_up/w"])
    return x + mm(a, w["mlp/wo/w"])


# ------------------------------------------------------------------ counts
SITES = ("attn.wq", "attn.wk", "attn.wv", "attn.qk", "attn.pv", "attn.wo",
         "mlp.wi", "mlp.wo", "unembed")


def site_cost(dims: dict, site: str, rows: int, ctx=()):
    """(flops, bytes) of one call of ``site`` over ``rows`` dispatched
    token rows.  For ``attn.qk``/``attn.pv`` ``ctx`` lists, per live
    sequence row, (query tokens dispatched, live context)."""
    d, H, KV, hd = dims["hidden"], dims["heads"], dims["kv_heads"], \
        dims["head_dim"]
    g = H // KV
    if site == "attn.wq":
        return gemm(rows, d, H * hd)
    if site in ("attn.wk", "attn.wv"):
        return gemm(rows, d, KV * hd)
    if site == "attn.wo":
        return gemm(rows, H * hd, d, resid=True)
    if site == "mlp.wi":
        return gemm(rows, d, dims["ffn"], w2=True)
    if site == "mlp.wo":
        return gemm(rows, dims["ffn"], d, resid=True)
    if site == "unembed":
        return gemm(rows, d, dims["vocab"], out=F32)
    fl = by = 0.0
    for q, c in ctx:                      # one (g*q, hd) x (hd, c) per KV head
        if site == "attn.qk":
            f, b = gemm(g * q, hd, c, out=F32)
        elif site == "attn.pv":
            f, b = gemm(g * q, c, hd)
        else:
            raise KeyError(site)
        fl += KV * f
        by += KV * b
    return fl, by


def kernel_calls_per_dispatch(dims: dict) -> int:
    """Kernel calls of one dispatched step: every site once per layer,
    ``unembed`` once."""
    return (len(SITES) - 1) * dims["layers"] + 1


def step_least_time(dims: dict, peak: dict, rows: int, head_rows: int,
                    ctx) -> float:
    """Least time of every kernel call of one dispatched step."""
    t = 0.0
    for site in SITES:
        r = head_rows if site == "unembed" else rows
        f, b = site_cost(dims, site, r, ctx)
        n = 1 if site == "unembed" else dims["layers"]
        t += n * max(f / peak["bf16_flops_per_s"], b / peak["hbm_bytes_per_s"])
    return t


def layer_matmul_params(dims: dict) -> int:
    d, H, KV, hd = dims["hidden"], dims["heads"], dims["kv_heads"], \
        dims["head_dim"]
    return d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * dims["ffn"]


def model_flops(dims: dict, prefill_rows, decode_ctx, stats) -> float:
    """FLOPs the served tokens need: live prompt tokens and decode tokens,
    attention over each token's live context, logits only where a token is
    sampled.  ``prefill_rows``: (first position, tokens) per prefilled
    chunk row; ``decode_ctx``: live context per decoded token; ``stats``,
    the interval's engine counters, adds nothing to a dense layer."""
    per_tok = 2.0 * layer_matmul_params(dims) * dims["layers"]
    attn = 4.0 * dims["heads"] * dims["head_dim"] * dims["layers"]
    total = 0.0
    for p, n in prefill_rows:             # positions p..p+n-1, contexts p+1..p+n
        total += n * per_tok + attn * (n * p + n * (n + 1) / 2.0)
    for c in decode_ctx:
        total += per_tok + attn * c + 2.0 * dims["hidden"] * dims["vocab"]
    return total
