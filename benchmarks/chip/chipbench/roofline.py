"""Operations and bytes of the served work, from shapes alone, and the
table of peaks keyed by ``device_kind``.

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

import json
from pathlib import Path

BF16 = 2
F32 = 4
SITES = ("attn.wq", "attn.wk", "attn.wv", "attn.qk", "attn.pv", "attn.wo",
         "mlp.wi", "mlp.wo", "unembed")


def peaks(kind: str, path: Path) -> dict:
    table = json.loads(Path(path).read_text())
    if kind not in table:
        raise KeyError(f"device_kind {kind!r} is not in the peak table "
                       f"({sorted(table)})")
    return table[kind]


def _gemm(m, k, n, *, w2=False, resid=False, out=BF16):
    """(flops, bytes) of one (m,k)@(k,n) call with bf16 operands."""
    nw = 2 if w2 else 1
    flops = 2.0 * m * k * n * nw
    by = BF16 * (m * k + nw * k * n) + out * m * n + (BF16 * m * n
                                                     if resid else 0)
    return flops, by


def site_cost(dims: dict, site: str, rows: int, ctx=()):
    """(flops, bytes) of one call of ``site`` over ``rows`` dispatched
    token rows.  For ``attn.qk``/``attn.pv`` ``ctx`` lists, per live
    sequence row, (query tokens dispatched, live context)."""
    d, H, KV, hd = dims["hidden"], dims["heads"], dims["kv_heads"], \
        dims["head_dim"]
    g = H // KV
    if site == "attn.wq":
        return _gemm(rows, d, H * hd)
    if site in ("attn.wk", "attn.wv"):
        return _gemm(rows, d, KV * hd)
    if site == "attn.wo":
        return _gemm(rows, H * hd, d, resid=True)
    if site == "mlp.wi":
        return _gemm(rows, d, dims["ffn"], w2=True)
    if site == "mlp.wo":
        return _gemm(rows, dims["ffn"], d, resid=True)
    if site == "unembed":
        return _gemm(rows, d, dims["vocab"], out=F32)
    fl = by = 0.0
    for q, c in ctx:                      # one (g*q, hd) x (hd, c) per KV head
        if site == "attn.qk":
            f, b = _gemm(g * q, hd, c, out=F32)
        elif site == "attn.pv":
            f, b = _gemm(g * q, c, hd)
        else:
            raise KeyError(site)
        fl += KV * f
        by += KV * b
    return fl, by


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


def model_flops(dims: dict, prefill_rows, decode_ctx) -> float:
    """FLOPs the served tokens need: live prompt tokens and decode tokens,
    attention over each token's live context, logits only where a token is
    sampled.  ``prefill_rows``: (first position, tokens) per prefilled
    chunk row; ``decode_ctx``: live context per decoded token."""
    per_tok = 2.0 * layer_matmul_params(dims) * dims["layers"]
    attn = 4.0 * dims["heads"] * dims["head_dim"] * dims["layers"]
    total = 0.0
    for p, n in prefill_rows:             # positions p..p+n-1, contexts p+1..p+n
        total += n * per_tok + attn * (n * p + n * (n + 1) / 2.0)
    for c in decode_ctx:
        total += per_tok + attn * c + 2.0 * dims["hidden"] * dims["vocab"]
    return total
