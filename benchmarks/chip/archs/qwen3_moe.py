"""The Qwen3-MoE decoder (Qwen3-30B-A3B), as the benchmark sees it: its
sizes, the program settings they map to, its layer's seeded leaves, its
plain float32 layer, and the operations and bytes of its served work.

A configuration file names this module in its ``reference`` key; the
harness loads it by path and calls the names in ``harness.ARCH_NAMES``.
Nothing here imports the program.

The layer follows the published Qwen3-MoE equations (Hugging Face
``modeling_qwen3_moe``): RMSNorm, Q/K/V projections without bias, RMSNorm
of each query and key head over ``head_dim`` (``q_norm``, ``k_norm``),
rotary position embedding over rotate-half pairs, causal grouped-query
attention with 1/sqrt(head_dim) scaling and a float32 softmax, output
projection without bias, residual, RMSNorm, then the sparse expert block:
a router over all ``num_experts`` experts, softmax, the top
``num_experts_per_tok``, their probabilities renormalised to sum to 1
(``norm_topk_prob``), and the weighted sum of those experts' SwiGLU MLPs
(silu(x Wg) * (x Wu)) Wd; residual.  Every product runs at
``Precision.HIGHEST`` (true float32 on a TPU).

Departures: the weights are the benchmark's seeded random weights
(``chipbench/weights.py``); the expert banks hold this chip's share, the
``num_experts`` of the configuration file from ``program.expert_first``
(the router keeps the published count, ``published.num_experts``), and the
layer returns that share's part of the expert block, as the program does
(the other chips' parts are left out of both); a sliced vocabulary is a
smaller vocabulary.

Every product of a step runs through one GEMM kernel per site: per layer
``attn.wq``, ``attn.wk``, ``attn.wv``, ``attn.qk``, ``attn.pv``,
``attn.wo``, ``moe.router`` (float32), ``moe.wi_gate``, ``moe.wi_up`` and
``moe.wo`` (each one launch over every held expert), and once per step
``unembed``.  A call's least time on the chip is the larger of its
operations over peak bf16 FLOP/s and its bytes over peak HBM bytes/s.
Shapes are logical: the rows the step dispatched, bf16 operands (the
router's float32), for ``qk`` and ``pv`` each row's live context, and for
the expert sites the routed rows, every held expert's weights read once.
``step_least_time`` is given no routing, so it takes the routed rows of a
dispatch as their expectation, rows x top-k x held / experts; the
program's kernels compute every held expert over every row.
"""
from __future__ import annotations

import math
from pathlib import Path

import jax
import jax.numpy as jnp

from chipbench.harness import load_arch
from chipbench.reference import HI, rmsnorm, rope
from chipbench.roofline import BF16, F32

# the attention sites and the head cost what they cost in Qwen2
_qwen2 = load_arch(Path(__file__).resolve().parent / "qwen2.py")

# the source config.json's keys -> the program's ModelConfig fields
HF_TO_PROGRAM = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "rms_norm_eps": "rms_eps",
    "tie_word_embeddings": "tie_embeddings",
    "attention_bias": "qkv_bias",
}


def dims(conf: dict) -> dict:
    """Sizes; ``experts`` is the router's width (the published count),
    ``held`` the experts this chip holds, from ``first``."""
    return dict(layers=conf["num_hidden_layers"], hidden=conf["hidden_size"],
                heads=conf["num_attention_heads"],
                kv_heads=conf["num_key_value_heads"],
                head_dim=conf["head_dim"], vocab=conf["vocab_size"],
                experts=conf.get("published", {}).get("num_experts",
                                                      conf["num_experts"]),
                held=conf["num_experts"],
                first=conf["program"].get("expert_first", 0),
                top_k=conf["num_experts_per_tok"],
                ffn=conf["moe_intermediate_size"])


def program_kwargs(conf: dict) -> dict:
    """The program's ModelConfig fields this configuration states; the
    expert group as a dict (``harness.model_config`` replaces those fields
    of the program's own)."""
    if not conf["norm_topk_prob"] or conf["mlp_only_layers"]:
        raise ValueError("the program renormalises the top-k probabilities "
                         "and has every layer sparse")
    d = dims(conf)
    kw = {HF_TO_PROGRAM[k]: conf[k] for k in HF_TO_PROGRAM}
    kw.update(qk_norm=True, sliding_window=0,
              moe=dict(num_experts=d["experts"], top_k=d["top_k"],
                       expert_d_ff=d["ffn"],
                       moe_every=conf["decoder_sparse_step"],
                       experts_held=d["held"], expert_first=d["first"]))
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
    "attn/wk/w": lambda d: (d["hidden"], d["kv_heads"] * d["head_dim"]),
    "attn/wv/w": lambda d: (d["hidden"], d["kv_heads"] * d["head_dim"]),
    "attn/q_norm/scale": lambda d: (d["head_dim"],),
    "attn/k_norm/scale": lambda d: (d["head_dim"],),
    "attn/wo/w": lambda d: (d["heads"] * d["head_dim"], d["hidden"]),
    "ln2/scale": lambda d: (d["hidden"],),
    "moe/router": lambda d: (d["hidden"], d["experts"]),
    "moe/wi_gate": lambda d: (d["held"], d["hidden"], d["ffn"]),
    "moe/wi_up": lambda d: (d["held"], d["hidden"], d["ffn"]),
    "moe/wo": lambda d: (d["held"], d["ffn"], d["hidden"]),
}


def route(h, router, top_k):
    """Softmax over every expert, the top ``top_k``, renormalised:
    (weights, expert ids), each (..., top_k)."""
    p = jax.nn.softmax(jnp.einsum("...k,kn->...n", h, router, precision=HI),
                       axis=-1)
    vals, idx = jax.lax.top_k(p, top_k)
    return vals / jnp.sum(vals, axis=-1, keepdims=True), idx


def layer(x, w, *, dims, eps, theta, q_block):
    """One decoder layer on x (N, L, d), the expert block's held share."""
    N, L, d = x.shape
    H, KV, hd = dims["heads"], dims["kv_heads"], dims["head_dim"]
    g = H // KV

    def mm(a, b):
        return jnp.einsum("...k,kn->...n", a, b, precision=HI)

    h = rmsnorm(x, w["ln1/scale"], eps)
    q = rmsnorm(mm(h, w["attn/wq/w"]).reshape(N, L, H, hd),
                w["attn/q_norm/scale"], eps)
    k = rmsnorm(mm(h, w["attn/wk/w"]).reshape(N, L, KV, hd),
                w["attn/k_norm/scale"], eps)
    v = mm(h, w["attn/wv/w"]).reshape(N, L, KV, hd)
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
    vals, idx = route(h, w["moe/router"], dims["top_k"])
    y = jnp.zeros_like(x)
    for e in range(dims["held"]):         # dense over the held experts
        wgt = jnp.sum(jnp.where(idx == dims["first"] + e, vals, 0.0), -1)
        a = (jax.nn.silu(mm(h, w["moe/wi_gate"][e]))
             * mm(h, w["moe/wi_up"][e]))
        y = y + wgt[..., None] * mm(a, w["moe/wo"][e])
    return x + y


# ------------------------------------------------------------------ counts
SITES = ("attn.wq", "attn.wk", "attn.wv", "attn.qk", "attn.pv", "attn.wo",
         "moe.router", "moe.wi_gate", "moe.wi_up", "moe.wo", "unembed")


def routed_rows(dims: dict, rows: int) -> float:
    """The expected (token, held expert) pairs of ``rows`` dispatched
    tokens when routing spreads evenly over the experts."""
    return rows * dims["top_k"] * dims["held"] / dims["experts"]


def site_cost(dims: dict, site: str, rows: int, ctx=()):
    """(flops, bytes) of one call of ``site`` over ``rows`` dispatched
    token rows.  For ``attn.qk``/``attn.pv`` ``ctx`` lists, per live
    sequence row, (query tokens dispatched, live context)."""
    d, E, Eh, f = dims["hidden"], dims["experts"], dims["held"], dims["ffn"]
    if site == "moe.router":
        return 2.0 * rows * d * E, F32 * (rows * d + d * E + rows * E)
    if site in ("moe.wi_gate", "moe.wi_up", "moe.wo"):
        r = routed_rows(dims, rows)
        k, n = (f, d) if site == "moe.wo" else (d, f)
        return 2.0 * r * k * n, BF16 * (r * k + Eh * k * n + r * n)
    return _qwen2.site_cost(dims, site, rows, ctx)


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


def model_flops(dims: dict, prefill_rows, decode_ctx, stats) -> float:
    """FLOPs the served tokens need: per live prompt and decode token the
    attention projections and the router in every layer, attention over
    its live context, logits only where a token is sampled; and the held
    experts' SwiGLU for each (token, held expert) pair the engine counted
    (``stats["moe_assignments"]``, summed over the layers; a program
    without that counter adds none).  ``prefill_rows``: (first position,
    tokens) per prefilled chunk row; ``decode_ctx``: live context per
    decoded token."""
    d, H, KV, hd = dims["hidden"], dims["heads"], dims["kv_heads"], \
        dims["head_dim"]
    per_layer = d * H * hd + 2 * d * KV * hd + H * hd * d + d * dims["experts"]
    per_tok = 2.0 * per_layer * dims["layers"]
    attn = 4.0 * H * hd * dims["layers"]
    total = 6.0 * d * dims["ffn"] * stats.get("moe_assignments", 0)
    for p, n in prefill_rows:             # positions p..p+n-1, contexts p+1..p+n
        total += n * per_tok + attn * (n * p + n * (n + 1) / 2.0)
    for c in decode_ctx:
        total += per_tok + attn * c + 2.0 * d * dims["vocab"]
    return total
