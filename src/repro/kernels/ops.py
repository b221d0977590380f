"""jit'd public wrappers: planner-driven kernel configuration.

``arrayflex_matmul`` is the framework's ArrayFlex-scheduled GEMM: the
collapse factor k comes from core.planner (Eq. 6/7) for the GEMM's (M,N,T)
shape, mirroring the paper's per-CNN-layer pipeline-depth selection, and an
optional fused epilogue (bias / activation / dual-GEMM gate) rides the
carry-propagate store.  ``arrayflex_expert_matmul`` runs a stack of
same-shape per-expert GEMMs in one launch.  ``attention`` picks the flash
kernel's KV-chunk with the same machinery.

``plan_collapse`` is memoized: it is a pure function of small int tuples,
and model tracing + per-request serving hit it with the same handful of
shapes thousands of times.

Pallas ``interpret`` resolution (``kernels.runtime.resolve_interpret``):
compiled on TPU backends, interpreted elsewhere; an explicit argument wins
off the TPU, and interpret mode on a TPU raises.
``ModelConfig.pallas_interpret`` threads the explicit argument from model
configs down to every kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import planner, timing
from repro.kernels.arrayflex_gemm import (apply_epilogue, arrayflex_gemm,
                                          arrayflex_expert_gemm)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.runtime import resolve_interpret

# MXU geometry: the TPU systolic tile the collapse factor schedules around.
SA_R = 128
SA_C = 128


@functools.lru_cache(maxsize=None)
def plan_collapse(M: int, K: int, T_rows: int, *, max_k: int = 4,
                  epilogue_ops: int = 0, precision: str = "fp32",
                  actq_ops: int = 0, transfer_cycles: int = 0) -> int:
    """ArrayFlex pipeline depth for GEMM X[T,K] @ W[K,M] (Eq. 7 -> discrete).

    K is the contraction (the SA's R-tiled dim), M the output columns.
    ``epilogue_ops`` prices fused post-GEMM vector ops into the per-step
    period (Eq. 5'), which can shift the argmin toward deeper collapse.
    ``precision`` selects the datapath's Eq.(5) coefficients
    (``timing.timing_for``): the int8 datapath's cheap collapse stages
    move the argmin deeper than fp32 picks at the same shape.
    ``actq_ops`` prices the W8A8 dynamic activation-quantize boundary
    stage (Eq. 5' ``d_actq_ps``); on the w8a8 datapath this term alone
    can deepen the argmin — e.g. (896, 4864, 512) picks k=2 unpriced and
    k=4 with the quantizer priced.  ``transfer_cycles`` serializes a
    pipeline-stage activation transfer (ICI ingress at C lanes/cycle) in
    front of the schedule — paid at the k-collapsed period (Eq. 6''), it
    pushes the argmin SHALLOWER, which is how a latency-bound decode
    stage legitimately plans a shallower k than a compute-bound prefill
    stage at the same (M, K, T).
    """
    k = timing.best_k(M, K, T_rows, SA_R, SA_C,
                      timing.timing_for(precision),
                      epilogue_ops=epilogue_ops, actq_ops=actq_ops,
                      extra_cycles=transfer_cycles)
    return max(1, min(max_k, k))


@functools.partial(jax.jit,
                   static_argnames=("activation", "has_w2", "has_b",
                                    "has_b2", "has_s", "has_s2", "has_r",
                                    "has_g", "act_quant", "k_collapse",
                                    "bk", "out_dtype", "interpret"))
def _gemm(x, w, w2, bias, bias2, w_scale, w2_scale, residual, norm_scale,
          activation, has_w2, has_b, has_b2, has_s, has_s2, has_r,
          has_g, act_quant: bool,
          k_collapse: int, bk: int, out_dtype, interpret: bool):
    return arrayflex_gemm(x, w,
                          w2=w2 if has_w2 else None,
                          bias=bias if has_b else None,
                          bias2=bias2 if has_b2 else None,
                          w_scale=w_scale if has_s else None,
                          w2_scale=w2_scale if has_s2 else None,
                          residual=residual if has_r else None,
                          norm_scale=norm_scale if has_g else None,
                          act_quant=act_quant,
                          activation=activation, bk=bk,
                          k_collapse=k_collapse, out_dtype=out_dtype,
                          interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("has_s", "act_quant", "k_collapse",
                                    "bk", "out_dtype", "interpret"))
def _expert_gemm(x, w, w_scale, has_s, act_quant: bool, k_collapse: int,
                 bk: int, out_dtype, interpret: bool):
    return arrayflex_expert_gemm(x, w,
                                 w_scale=w_scale if has_s else None,
                                 act_quant=act_quant,
                                 bk=bk, k_collapse=k_collapse,
                                 out_dtype=out_dtype, interpret=interpret)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def arrayflex_matmul(x, w, *, w2=None, bias=None, bias2=None,
                     w_scale=None, w2_scale=None, act_quant: bool = False,
                     residual=None, norm_scale=None,
                     activation: str = "none", k_collapse: int = 0,
                     bk: int = 128, out_dtype=None, interpret=None):
    """Planner-configured GEMM with fused epilogue.  x: (..., K), w: (K, N).

        out = [residual +] act((g*x)@w [+ bias]) [* ((g*x)@w2 [+ bias2])]

    ``norm_scale`` (``g``, a (K,) vector) fuses the rmsnorm elementwise
    scale into the kernel's step prologue — one more priced boundary op,
    no separate scale pass before the GEMM.

    ``residual`` is an output-shaped ``(..., N)`` stream joined after the
    activation/gate at the carry-propagate store (one more priced
    boundary op; padded rows/columns join zero residual and slice off).

    ``w_scale`` enables the int8-weight path (``w`` holds int8 codes,
    effective weight ``w * w_scale`` per output channel; dequant at the
    carry-propagate store) — the unplanned ``k_collapse=0`` then picks k
    with the int8 datapath's Eq.(5) coefficients, which favor deeper
    collapse than fp32.  ``act_quant`` (requires ``w_scale``) enables the
    W8A8 per-tile activation quantize + int8 x int8 -> int32 chain; the
    unplanned path then prices the w8a8 datapath with one Eq.(5')
    activation-quantize boundary op.

    Covers *every* nonempty shape exactly: the kernel zero-pads ragged K
    itself, and ragged M rows / N columns (tilings the output grid cannot
    absorb) are zero-padded here to the systolic tile and sliced off the
    result — zeros contribute exactly 0 to the fp32 accumulator, so
    padding is exact and no reference fallback is ever taken.  Padded N
    columns extend ``bias``/``bias2`` (and the dequant scales) with zeros
    (sliced off with the output); padded M rows run the epilogue on zero
    accumulators and are sliced off.
    """
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = w.shape[-1]
    out_dtype = out_dtype or x.dtype
    interpret = resolve_interpret(interpret)
    if x.size == 0 or N == 0 or K == 0:   # empty operand: epilogue of zeros
        zero = jnp.zeros((*lead, N), jnp.float32)
        out = apply_epilogue(
            zero, zero if w2 is not None else None,
            None if bias is None else bias.astype(jnp.float32),
            None if bias2 is None else bias2.astype(jnp.float32),
            activation)
        if residual is not None:
            out = residual.astype(jnp.float32) + out
        return out.astype(out_dtype)
    x2 = x.reshape(-1, K)
    M_rows = x2.shape[0]
    quant = w_scale is not None
    if not k_collapse:
        # dequant multiplies (one per contraction) and the residual join
        # are boundary ops too
        n_ops = ((activation != "none") + (bias is not None)
                 + (bias2 is not None) + (w2 is not None)
                 + (residual is not None) + (norm_scale is not None)
                 + quant * (1 + (w2 is not None)))
        precision = ("w8a8" if act_quant else "int8") if quant else "fp32"
        k_collapse = plan_collapse(N, K, M_rows, epilogue_ops=n_ops,
                                   precision=precision,
                                   actq_ops=int(act_quant))
    # tile sizes mirror the kernel's bm/bn clamp: a dim smaller than the SA
    # is its own (exactly dividing) tile; larger dims pad up to a multiple.
    Mp = M_rows if M_rows <= SA_R else _round_up(M_rows, SA_R)
    Np = N if N <= SA_C else _round_up(N, SA_C)
    if residual is not None:
        residual = residual.reshape(M_rows, N)
        if (Mp, Np) != (M_rows, N):
            residual = jnp.pad(residual, ((0, Mp - M_rows), (0, Np - N)))
    if Mp != M_rows:
        x2 = jnp.pad(x2, ((0, Mp - M_rows), (0, 0)))
    if Np != N:
        w = jnp.pad(w, ((0, 0), (0, Np - N)))
        if w2 is not None:
            w2 = jnp.pad(w2, ((0, 0), (0, Np - N)))
        if bias is not None:
            bias = jnp.pad(bias, (0, Np - N))
        if bias2 is not None:
            bias2 = jnp.pad(bias2, (0, Np - N))
        if w_scale is not None:
            w_scale = jnp.pad(w_scale, (0, Np - N))
        if w2_scale is not None:
            w2_scale = jnp.pad(w2_scale, (0, Np - N))
    dummy = jnp.zeros((), x2.dtype)
    out = _gemm(x2, w,
                w2 if w2 is not None else dummy,
                bias if bias is not None else dummy,
                bias2 if bias2 is not None else dummy,
                w_scale if w_scale is not None else dummy,
                w2_scale if w2_scale is not None else dummy,
                residual if residual is not None else dummy,
                norm_scale if norm_scale is not None else dummy,
                activation, w2 is not None, bias is not None,
                bias2 is not None, w_scale is not None,
                w2_scale is not None, residual is not None,
                norm_scale is not None,
                act_quant, k_collapse, bk,
                out_dtype, interpret)
    if (Mp, Np) != (M_rows, N):
        out = out[:M_rows, :N]
    return out.reshape(*lead, N)


def arrayflex_expert_matmul(x, w, *, w_scale=None, act_quant: bool = False,
                            k_collapse: int = 0,
                            bk: int = 128, out_dtype=None, interpret=None):
    """Planner-configured batched expert GEMM in ONE kernel launch.

    x: (E, T, K), w: (E, K, N) -> (E, T, N).  All experts share one
    collapse depth k, planned for the common (N, K, T) shape (every expert
    GEMM in a capacity-buffered MoE layer has identical shape).
    ``w_scale`` (E, N) enables the int8-weight path; ``act_quant`` adds
    the W8A8 per-tile activation quantize.  Ragged T / N are zero-padded
    to the systolic tile and sliced off, exactly as in
    :func:`arrayflex_matmul`.
    """
    E, T, K = x.shape
    N = w.shape[-1]
    out_dtype = out_dtype or x.dtype
    interpret = resolve_interpret(interpret)
    if E == 0 or T == 0 or N == 0 or K == 0:
        return jnp.zeros((E, T, N), out_dtype)
    quant = w_scale is not None
    if not k_collapse:
        precision = ("w8a8" if act_quant else "int8") if quant else "fp32"
        k_collapse = plan_collapse(N, K, T, epilogue_ops=int(quant),
                                   precision=precision,
                                   actq_ops=int(act_quant))
    Tp = T if T <= SA_R else _round_up(T, SA_R)
    Np = N if N <= SA_C else _round_up(N, SA_C)
    if Tp != T:
        x = jnp.pad(x, ((0, 0), (0, Tp - T), (0, 0)))
    if Np != N:
        w = jnp.pad(w, ((0, 0), (0, 0), (0, Np - N)))
        if w_scale is not None:
            w_scale = jnp.pad(w_scale, ((0, 0), (0, Np - N)))
    dummy = jnp.zeros((), x.dtype)
    out = _expert_gemm(x, w, w_scale if quant else dummy, quant, act_quant,
                       k_collapse, bk, out_dtype, interpret)
    if (Tp, Np) != (T, N):
        out = out[:, :T, :N]
    return out


def attention(q, k, v, *, causal=True, window=0, kv_chunk: int = 0,
              interpret=None):
    """Flash attention with planner-chosen KV chunk.  (BH,S,D) layout.

    The KV length need not divide the chunk: the kernel pads K/V to the
    chunk grid and masks the tail, so the planner's pick is used as-is
    (a prime KV length no longer degenerates to chunk=1).
    """
    if not kv_chunk:
        kv_chunk = planner.attention_plan(q.shape[1], k.shape[1])
    return flash_attention(q, k, v, causal=causal, window=window,
                           kv_chunk=kv_chunk,
                           interpret=resolve_interpret(interpret))
