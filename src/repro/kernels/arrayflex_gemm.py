"""ArrayFlex GEMM as a Pallas TPU kernel: configurable K-collapse with
fused epilogues and an expert-batched variant.

TPU adaptation of the paper's configurable transparent pipelining (DESIGN.md
§Hardware adaptation): the MXU is itself a 128x128 systolic array whose
pipeline we cannot touch, but the *grid schedule* around it exposes the same
cycles-vs-per-step-cost tradeoff.  The collapse factor k fuses k consecutive
K-panels into ONE grid step:

  * fewer sequential grid steps  (the paper's R/k + C/k cycle reduction),
  * larger per-step VMEM working set and serial in-step adder chain
    (the paper's k*(d_CSA + 2 d_mux) clock-period increase),
  * the fp32 VMEM accumulator plays the carry-save register chain: partial
    sums stay in "redundant" form across the k sub-tiles and the final
    cast/store is the carry-propagate add at the collapsed-block boundary.

That carry-propagate boundary is exactly where an **epilogue** belongs:
bias add, activation, the gated multiply of a second fused contraction
(dual-GEMM swiglu: ``silu(x@w + b) * (x@w2 + b2)``), and the transformer
sublayer's residual join (``residual + f(x)``, applied after the
activation/gate) are applied to the resolved fp32 accumulator *before*
the single cast/store, so neither the activation nor the residual add
round-trips through HBM.  Eq.(5') in core.timing prices
the fused vector ops into the per-step period and ``best_k`` re-picks k.

The boundary also hosts **int8 dequantization** (``w_scale``/``w2_scale``):
the contraction streams raw int8 weight codes into the fp32 accumulator
and the per-output-channel scale multiply resolves with the
carry-propagate — per-column scales factor out of the K sum, so the
deferred dequant is exact and rides the same boundary ALU the epilogue
does (one extra Eq.(5') op per contraction, priced by
``timing.IntTimingParams``'s int8 datapath coefficients).

The **W8A8** path (``act_quant=True``) adds the other half: each grid
step's activation tile is quantized to int8 with a dynamic symmetric
per-tile fp32 scale in the step prologue — amax over the (bm, kk) tile,
reciprocal scale, round/clip — and the k-deep chain then runs real
int8 x int8 -> int32 MXU passes.  The two scales resolve at different
boundaries, both exact: the per-tile *activation* scale differs per
K-step, so it folds into the fp32 carry accumulator as each step's int32
partial resolves (sum_s x_scale_s * iacc_s); the per-output-channel
*weight* scale is constant across K, factors out of the whole sum, and
rides the carry-propagate ``store_phase`` dequant exactly as in the
weight-only path.  The quantizer stage is priced as the Eq.(5')
``d_actq_ps`` boundary term (``timing.W8A8TimingParams``).  The int32
accumulator cannot overflow: |code| <= 127, so one collapsed block of
kk <= bk * k_collapse = 512 MACs is bounded by 512 * 127^2 ~ 8.3e6,
far inside int32 range.

``arrayflex_expert_gemm`` runs a whole stack of per-expert GEMMs in ONE
``pallas_call`` whose *leading grid dimension is the expert axis* — the
MoE layer's 3E per-layer kernel launches become 3.

core.planner.best_k picks k per GEMM shape exactly as the paper picks the
pipeline depth per CNN layer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret

# Epilogue activations applicable at the carry-propagate boundary.
ACTIVATIONS = ("none", "silu", "gelu")


def _act(y, activation: str):
    if activation == "none":
        return y
    if activation == "silu":
        return jax.nn.silu(y)
    if activation == "gelu":
        return jax.nn.gelu(y)
    raise ValueError(f"unknown epilogue activation {activation!r}; "
                     f"supported: {ACTIVATIONS}")


def apply_epilogue(y, y2=None, bias=None, bias2=None, activation="none"):
    """The epilogue's reference semantics, shared by the fused kernel's
    store phase and every unfused backend:

        out = act(y [+ bias]) [* (y2 [+ bias2])]

    Operates in the dtype of ``y`` (fp32 inside the kernel; the operands'
    dtype on the unfused xla path, reproducing the pre-fusion op order
    bit for bit).
    """
    if bias is not None:
        y = y + bias.astype(y.dtype)
    out = _act(y, activation)
    if y2 is not None:
        if bias2 is not None:
            y2 = y2 + bias2.astype(y2.dtype)
        out = out * y2
    return out


def prologue_phase(x, norm_scale):
    """The grid step's *prologue* boundary math — the rmsnorm elementwise
    scale fused in front of the contraction: multiply the activation tile
    by the per-input-channel ``g`` in fp32 and cast back to the operand
    dtype.

    This is the SINGLE definition of the fused norm-scale (the kernels
    inline it on each x tile, the unfused backends apply it to the whole
    x, and ``analysis.kernel_check`` traces it to count the boundary op
    against ``Epilogue.ops``).  Because ``nn.layers.rmsnorm_normalize``
    hands the substrate an already-cast normalized x, every backend
    computes the identical ``(x_f32 * g) -> cast`` expression and fused
    vs unfused outputs agree bit for bit.

    Unlike the store-boundary ops, the scale is per-*input*-channel — it
    cannot commute past the K sum to the carry-propagate store, which is
    why it rides the step prologue (the same slot the W8A8 activation
    quantizer occupies) rather than ``store_phase``.
    """
    if norm_scale is None:
        return x
    return (x.astype(jnp.float32)
            * norm_scale.astype(jnp.float32)).astype(x.dtype)


def quantize_tile(x, eps: float = 1e-12):
    """Dynamic symmetric per-tile activation quantization: the W8A8 grid
    step's prologue stage, and the SINGLE definition of the quantizer
    (the kernels inline it; the property tests and the analysis passes
    trace this exact function).

    Returns ``(codes, scale)`` with ``codes`` int8 in [-127, 127] and
    ``scale`` a per-tile fp32 scalar such that ``codes * scale ~= x``
    with error bounded by ``scale / 2 = amax / 254`` per element.  An
    all-zero tile quantizes to all-zero codes (the eps floor keeps the
    reciprocal finite), so zero K-padding tails contribute exactly 0.
    """
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32))
    scale = jnp.maximum(amax, eps) / 127.0
    codes = jnp.clip(jnp.round(x32 / scale), -127, 127).astype(jnp.int8)
    return codes, scale


def store_phase(y, y2=None, w_scale=None, w2_scale=None, bias=None,
                bias2=None, activation="none", residual=None):
    """The carry-propagate boundary math, in execution order: dequant the
    resolved fp32 accumulator(s), the fused epilogue, then the residual
    join (``residual + f(x)`` — the sublayer add applies to the finished
    activation/gate output, matching the unfused layers' op order).

    This is the SINGLE definition of what the kernel store applies —
    ``_kernel``/``_expert_kernel`` call it on their accumulator refs, and
    ``analysis.kernel_check`` traces it to count the boundary ops actually
    executed against the ``Epilogue.ops`` pricing (Eq. 5' ``e``), so the
    timing model and the datapath cannot drift apart silently.
    """
    if w_scale is not None:
        y = y * w_scale.astype(jnp.float32)
    if y2 is not None and w2_scale is not None:
        y2 = y2 * w2_scale.astype(jnp.float32)
    out = apply_epilogue(
        y, y2,
        None if bias is None else bias.astype(jnp.float32),
        None if bias2 is None else bias2.astype(jnp.float32),
        activation)
    if residual is not None:
        out = residual.astype(jnp.float32) + out
    return out


# Lane width of a TPU vector register: Mosaic accepts a block (and an
# in-kernel slice) whose last dim is a multiple of it, or the whole axis.
LANE = 128


def k_tiling(K: int, bk: int, k_collapse: int):
    """Lane-aligned K schedule -> ``(k, n_steps, kk, K_pad)``.

    Each of the ``k`` sub-tiles of a collapsed block is a multiple of
    :data:`LANE` wide (``bk`` rounds up to one), so the x block
    ``(bm, kk)`` and every in-kernel slice ``x[:, i*bk:(i+1)*bk]`` sit on
    lane boundaries.  ``K`` pads with zeros to ``K_pad = n_steps * kk``
    (exact: zero columns add 0 to the accumulator); e.g. K=896 pads to
    1024 at k=2 and k=4.  Two shapes need no padding: a K that fits one
    uncollapsed sub-tile is its own full-width block, and a collapse
    deeper than K's lane-tile count is clamped, since the extra sub-tiles
    would hold nothing but padding."""
    bk = -(-bk // LANE) * LANE
    k = max(1, min(k_collapse, -(-K // LANE)))
    n_steps = -(-K // (bk * k))
    if n_steps == 1 and k == 1:
        return 1, 1, K, K
    bk_eff = -(-K // (n_steps * k * LANE)) * LANE
    kk = bk_eff * k
    return k, n_steps, kk, n_steps * kk


# ---------------------------------------------------------------------------
# single-GEMM kernel (optionally dual-contraction) with fused epilogue

def _kernel(*refs, k_collapse: int, n_steps: int, activation: str,
            dual: bool, quant: bool, act_quant: bool, has_b: bool,
            has_b2: bool, has_r: bool, has_g: bool):
    """refs = x, w, [w2], [scale], [scale2], [b], [b2], [r], [g], o, acc,
    [acc2] (inputs, outputs, scratch — in pallas_call order).  ``has_r``:
    an (M, N) residual stream tiled like the output joins at the store,
    after the activation/gate.  ``has_g``: a (K,) rmsnorm scale, tiled
    with x's K axis, multiplies this step's x tile in the prologue
    (:func:`prologue_phase`) before the contraction — and before the
    W8A8 quantizer, so the quantizer sees the same values the unfused
    path would hand it.

    ``quant``: w (and w2) hold int8 codes with per-output-channel fp32
    scales; the contraction accumulates the raw codes and the dequant
    multiply resolves at the carry-propagate ``_store`` — the per-column
    scale factors out of the K sum, so deferring it is exact and the
    scale rides the same boundary ALU the epilogue does.

    ``act_quant`` (W8A8, requires ``quant``): the step's x-tile is
    quantized to int8 with one dynamic per-tile fp32 scale in the
    prologue, the k-chain runs int8 x int8 -> int32 dots, and the int32
    partial folds into the fp32 carry accumulator scaled by this step's
    tile scale (per-step fold: the scale differs across K-steps, so only
    the K-constant weight scale defers to the store)."""
    i = 2
    x_ref, w_ref = refs[0], refs[1]
    w2_ref = refs[i] if dual else None
    i += dual
    s_ref = refs[i] if quant else None
    i += quant
    s2_ref = refs[i] if (quant and dual) else None
    i += quant and dual
    b_ref = refs[i] if has_b else None
    i += has_b
    b2_ref = refs[i] if has_b2 else None
    i += has_b2
    r_ref = refs[i] if has_r else None
    i += has_r
    g_ref = refs[i] if has_g else None
    i += has_g
    o_ref = refs[i]
    acc_ref = refs[i + 1]
    acc2_ref = refs[i + 2] if dual else None

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if dual:
            acc2_ref[...] = jnp.zeros_like(acc2_ref)

    x = x_ref[...]                     # (bm, bk * k)
    if has_g:                          # prologue: fused rmsnorm scale on
        x = prologue_phase(x, g_ref[...])   # this step's K slice
    w = w_ref[...]                     # (bk * k, bn)
    w2 = w2_ref[...] if dual else None
    if quant and not act_quant:        # int8 codes ride the MXU in x's dtype
        w = w.astype(x.dtype)          # (exact: |code| <= 127)
        if dual:
            w2 = w2.astype(x.dtype)
    bk = x.shape[1] // k_collapse
    acc = acc_ref[...]
    acc2 = acc2_ref[...] if dual else None
    if act_quant:
        # W8A8: quantize this step's x-tile once (the Eq.(5') d_actq
        # boundary stage), run the k-chain as int8 x int8 -> int32, and
        # fold the per-tile scale as the int32 partial resolves.  Bound:
        # kk <= 512 codes of |.| <= 127 -> |iacc| <= 512 * 127^2, no
        # int32 overflow.
        qx, x_scale = quantize_tile(x)
        iacc = jnp.zeros(acc_ref.shape, jnp.int32)
        iacc2 = jnp.zeros(acc_ref.shape, jnp.int32) if dual else None
        for i in range(k_collapse):
            qs = qx[:, i * bk:(i + 1) * bk]
            ws = slice(i * bk, (i + 1) * bk)
            iacc = iacc + jnp.dot(qs, w[ws, :],
                                  preferred_element_type=jnp.int32)
            if dual:
                iacc2 = iacc2 + jnp.dot(qs, w2[ws, :],
                                        preferred_element_type=jnp.int32)
        acc = acc + iacc.astype(jnp.float32) * x_scale
        if dual:
            acc2 = acc2 + iacc2.astype(jnp.float32) * x_scale
    else:
        # the k-deep "carry-save" chain: k MXU passes accumulate into the
        # same fp32 VMEM accumulator within one grid step (both
        # contractions stream through the same collapsed schedule when
        # dual)
        for i in range(k_collapse):
            xs = x[:, i * bk:(i + 1) * bk]
            ws = slice(i * bk, (i + 1) * bk)
            acc = acc + jnp.dot(xs, w[ws, :],
                                preferred_element_type=jnp.float32)
            if dual:
                acc2 = acc2 + jnp.dot(xs, w2[ws, :],
                                      preferred_element_type=jnp.float32)
    acc_ref[...] = acc
    if dual:
        acc2_ref[...] = acc2

    @pl.when(pl.program_id(2) == n_steps - 1)
    def _store():                      # carry-propagate: resolve the fp32
        out = store_phase(             # accumulator(s), dequant, fuse the
            acc_ref[...],              # epilogue, cast/store ONCE
            acc2_ref[...] if dual else None,
            s_ref[...] if quant else None,
            s2_ref[...] if (quant and dual) else None,
            b_ref[...] if has_b else None,
            b2_ref[...] if has_b2 else None,
            activation,
            r_ref[...] if has_r else None)
        o_ref[...] = out.astype(o_ref.dtype)


def arrayflex_gemm(x, w, *, w2=None, bias=None, bias2=None,
                   w_scale=None, w2_scale=None, act_quant: bool = False,
                   residual=None, norm_scale=None,
                   activation: str = "none", bm: int = 128, bn: int = 128,
                   bk: int = 128, k_collapse: int = 1, out_dtype=None,
                   interpret=None):
    """X[M,K] @ W[K,N] with K-collapse factor k_collapse and an optional
    fused epilogue at the carry-propagate boundary:

        out = [residual +] act(X@W [+ bias]) [* (X@W2 [+ bias2])]

    ``residual`` (an (M, N) array, any float dtype) fuses the sublayer
    residual join into the store: it is tiled exactly like the output,
    cast to fp32, and added after the activation/gate — one more Eq.(5')
    boundary op, no separate HBM round-trip for the add.

    ``norm_scale`` (a (K,) vector) fuses the rmsnorm elementwise scale
    into each grid step's *prologue* (:func:`prologue_phase`): the step's
    x tile is multiplied by its K-slice of ``g`` in fp32 and cast back
    before the contraction (and before the W8A8 quantizer) — the
    pre-attention norm's scale pass stops being a separate elementwise
    kernel on the decode hot path.  One more priced Eq.(5') boundary op.

    ``w2`` (same shape as ``w``) enables the dual-contraction gated form —
    with ``activation="silu"`` this is the one-kernel swiglu.  ``bias`` /
    ``bias2`` are (N,) vectors added to the fp32 accumulator(s) before the
    activation/gate.  All epilogue math happens on the resolved fp32
    accumulator; the output is cast exactly once.

    ``w_scale`` (an (N,) fp32 vector) enables the **int8-weight** path:
    ``w`` then holds int8 codes and the effective weight is
    ``w * w_scale`` per output channel.  The contraction accumulates raw
    codes in fp32 and the dequant multiply resolves at the carry-propagate
    store, *before* bias/activation — per-column scales factor out of the
    K sum, so deferring the dequant to the boundary is exact.  A dual
    contraction takes its own ``w2_scale``.

    ``act_quant`` (requires ``w_scale``) enables the **W8A8** path: each
    grid step quantizes its activation tile to int8 with a dynamic
    per-tile fp32 scale and the MAC chain runs int8 x int8 -> int32; the
    tile scale folds per step, the weight scale at the store (see the
    module docstring).  Unlike the weight path this is *lossy* on the
    activations (per-tile round-off bounded by amax/254 per element
    pre-contraction), so it is opt-in per site.

    Divisibility contract:
      * ``bm`` (clamped to M) must divide M and ``bn`` (clamped to N) must
        divide N — otherwise a ``ValueError`` is raised;
      * empty M, N or K short-circuits: the epilogue is applied to the
        exact zero accumulator(s) (NOT necessarily a zero result — a bias
        epilogue with K=0 returns ``act(bias)``);
      * K may be anything.  The K axis is tiled by :func:`k_tiling` into
        ``n_steps`` collapsed blocks of ``k_collapse`` equal lane-aligned
        sub-tiles each; when K does not fill that grid exactly, X and W
        are zero-padded along K (zeros contribute exactly 0 to the fp32
        accumulator, so the result is exact — previously the kernel
        silently *dropped* trailing K columns whenever the clamped block
        was not divisible by k_collapse, e.g. K=130, k_collapse=4).
    """
    M, K = x.shape
    K2, N = w.shape
    if K != K2:
        raise ValueError(f"contraction mismatch: x {x.shape} @ w {w.shape}")
    if k_collapse < 1:
        raise ValueError(f"k_collapse must be >= 1, got {k_collapse}")
    if activation not in ACTIVATIONS:
        raise ValueError(f"unknown epilogue activation {activation!r}; "
                         f"supported: {ACTIVATIONS}")
    dual = w2 is not None
    if dual and w2.shape != w.shape:
        raise ValueError(f"w2 {w2.shape} must match w {w.shape}")
    if bias2 is not None and not dual:
        raise ValueError("bias2 requires w2 (the dual contraction)")
    quant = w_scale is not None
    if w2_scale is not None and not (quant and dual):
        raise ValueError("w2_scale requires both w_scale and w2")
    if quant and dual and w2_scale is None:
        raise ValueError("int8 dual contraction needs w2_scale for w2")
    if act_quant and not quant:
        raise ValueError("act_quant (W8A8) requires int8 weights (w_scale)")
    for name, b in (("bias", bias), ("bias2", bias2),
                    ("w_scale", w_scale), ("w2_scale", w2_scale)):
        if b is not None and b.shape != (N,):
            raise ValueError(f"{name} must be ({N},), got {b.shape}")
    if residual is not None and residual.shape != (M, N):
        raise ValueError(
            f"residual must be ({M}, {N}), got {residual.shape}")
    if norm_scale is not None and norm_scale.shape != (K,):
        raise ValueError(
            f"norm_scale must be ({K},), got {norm_scale.shape}")
    out_dtype = out_dtype or x.dtype
    if M == 0 or N == 0 or K == 0:      # empty operand: epilogue of zeros
        zero = jnp.zeros((M, N), jnp.float32)
        out = apply_epilogue(zero, zero if dual else None,
                             None if bias is None else bias.astype(jnp.float32),
                             None if bias2 is None else bias2.astype(jnp.float32),
                             activation)
        if residual is not None:
            out = residual.astype(jnp.float32) + out
        return out.astype(out_dtype)
    bm, bn = min(bm, M), min(bn, N)
    if M % bm or N % bn:
        raise ValueError(
            f"bm must divide M and bn must divide N: "
            f"M={M}, bm={bm}, N={N}, bn={bn}")
    k_collapse, n_steps, kk, K_pad = k_tiling(K, bk, k_collapse)
    if K_pad != K:
        x = jnp.pad(x, ((0, 0), (0, K_pad - K)))
        w = jnp.pad(w, ((0, K_pad - K), (0, 0)))
        if dual:
            w2 = jnp.pad(w2, ((0, K_pad - K), (0, 0)))
        if norm_scale is not None:      # padded x columns are zero, so the
            norm_scale = jnp.pad(norm_scale, (0, K_pad - K))   # pad value
    grid = (M // bm, N // bn, n_steps)  # is inert (0 * 0 == 0)
    interpret = resolve_interpret(interpret)
    kernel = functools.partial(_kernel, k_collapse=k_collapse,
                               n_steps=n_steps, activation=activation,
                               dual=dual, quant=quant, act_quant=act_quant,
                               has_b=bias is not None,
                               has_b2=bias2 is not None,
                               has_r=residual is not None,
                               has_g=norm_scale is not None)
    operands = [x, w]
    in_specs = [
        pl.BlockSpec((bm, kk), lambda i, j, s: (i, s)),
        pl.BlockSpec((kk, bn), lambda i, j, s: (s, j)),
    ]
    if dual:
        operands.append(w2)
        in_specs.append(pl.BlockSpec((kk, bn), lambda i, j, s: (s, j)))
    for b in (w_scale, w2_scale, bias, bias2):
        if b is not None:
            operands.append(b.reshape(1, N))
            in_specs.append(pl.BlockSpec((1, bn), lambda i, j, s: (0, j)))
    if residual is not None:            # output-tiled: one (bm, bn) block
        operands.append(residual)
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)))
    if norm_scale is not None:          # K-tiled: this step's (kk,) slice
        operands.append(norm_scale.reshape(1, K_pad))
        in_specs.append(pl.BlockSpec((1, kk), lambda i, j, s: (0, s)))
    scratch = [pltpu.VMEM((bm, bn), jnp.float32)]
    if dual:
        scratch.append(pltpu.VMEM((bm, bn), jnp.float32))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(*operands)


# ---------------------------------------------------------------------------
# expert-batched kernel: the expert axis is the leading grid dimension

def _expert_kernel(*refs, k_collapse: int, n_steps: int, quant: bool,
                   act_quant: bool):
    """refs = x, w, [scale], o, acc.  ``quant``: int8 per-expert codes
    with per-(expert, output-channel) scales dequantized at the store.
    ``act_quant``: W8A8 — this expert's x-tile quantizes with one dynamic
    per-tile scale and the chain runs int8 x int8 -> int32, exactly as in
    :func:`_kernel`."""
    x_ref, w_ref = refs[0], refs[1]
    s_ref = refs[2] if quant else None
    o_ref = refs[2 + quant]
    acc_ref = refs[3 + quant]

    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0]                       # (bm, bk * k)  — this expert's rows
    w = w_ref[0]                       # (bk * k, bn)  — this expert's weights
    if quant and not act_quant:
        w = w.astype(x.dtype)          # exact: |code| <= 127
    bk = x.shape[1] // k_collapse
    acc = acc_ref[...]
    if act_quant:
        qx, x_scale = quantize_tile(x)
        iacc = jnp.zeros(acc_ref.shape, jnp.int32)
        for i in range(k_collapse):
            iacc = iacc + jnp.dot(qx[:, i * bk:(i + 1) * bk],
                                  w[i * bk:(i + 1) * bk, :],
                                  preferred_element_type=jnp.int32)
        acc = acc + iacc.astype(jnp.float32) * x_scale
    else:
        for i in range(k_collapse):
            acc = acc + jnp.dot(x[:, i * bk:(i + 1) * bk],
                                w[i * bk:(i + 1) * bk, :],
                                preferred_element_type=jnp.float32)
    acc_ref[...] = acc

    @pl.when(pl.program_id(3) == n_steps - 1)
    def _store():                      # carry-propagate: resolve, dequant,
        y = store_phase(acc_ref[...],  # cast once
                        w_scale=s_ref[0] if quant else None)
        o_ref[0] = y.astype(o_ref.dtype)


def arrayflex_expert_gemm(x, w, *, w_scale=None, act_quant: bool = False,
                          bm: int = 128,
                          bn: int = 128, bk: int = 128, k_collapse: int = 1,
                          out_dtype=None, interpret=None):
    """Batched per-expert GEMM in ONE launch: X[E,T,K] @ W[E,K,N] -> [E,T,N].

    ``w_scale`` (an (E, N) fp32 array) enables the int8-weight path: ``w``
    holds int8 codes and each expert's per-output-channel dequant multiply
    resolves at its carry-propagate store, exactly as in
    :func:`arrayflex_gemm`.  ``act_quant`` (requires ``w_scale``) adds the
    W8A8 per-tile activation quantize + int8 x int8 -> int32 chain.

    Grid = (E, T/bm, N/bn, n_steps) — the *leading* grid dimension walks
    the expert axis, so every expert's K-collapsed schedule runs inside a
    single ``pallas_call`` (the MoE layer's per-site dispatch count drops
    from E to 1).  Each (e, i, j) output tile owns the same fp32
    carry-save accumulator walk as :func:`arrayflex_gemm`; experts share
    the collapse depth k, planned once for the common (T, K, N) shape.

    Same divisibility contract as :func:`arrayflex_gemm` on T (rows) and
    N; K is zero-padded to the collapsed-block grid; empty E/T/N/K
    returns exact zeros.
    """
    E, T, K = x.shape
    E2, K2, N = w.shape
    if E != E2 or K != K2:
        raise ValueError(f"expert gemm mismatch: x {x.shape} @ w {w.shape}")
    if k_collapse < 1:
        raise ValueError(f"k_collapse must be >= 1, got {k_collapse}")
    quant = w_scale is not None
    if quant and w_scale.shape != (E, N):
        raise ValueError(f"w_scale must be ({E}, {N}), got {w_scale.shape}")
    if act_quant and not quant:
        raise ValueError("act_quant (W8A8) requires int8 weights (w_scale)")
    out_dtype = out_dtype or x.dtype
    if E == 0 or T == 0 or N == 0 or K == 0:
        return jnp.zeros((E, T, N), out_dtype)
    bm, bn = min(bm, T), min(bn, N)
    if T % bm or N % bn:
        raise ValueError(
            f"bm must divide T and bn must divide N: "
            f"T={T}, bm={bm}, N={N}, bn={bn}")
    k_collapse, n_steps, kk, K_pad = k_tiling(K, bk, k_collapse)
    if K_pad != K:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, K_pad - K)))
        w = jnp.pad(w, ((0, 0), (0, K_pad - K), (0, 0)))
    grid = (E, T // bm, N // bn, n_steps)
    interpret = resolve_interpret(interpret)
    kernel = functools.partial(_expert_kernel, k_collapse=k_collapse,
                               n_steps=n_steps, quant=quant,
                               act_quant=act_quant)
    operands = [x, w]
    in_specs = [
        pl.BlockSpec((1, bm, kk), lambda e, i, j, s: (e, i, s)),
        pl.BlockSpec((1, kk, bn), lambda e, i, j, s: (e, s, j)),
    ]
    if quant:                           # (E, 1, N): the block's last two
        operands.append(w_scale.reshape(E, 1, N))   # dims are (1, bn)
        in_specs.append(pl.BlockSpec((1, 1, bn),
                                     lambda e, i, j, s: (e, 0, j)))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bm, bn), lambda e, i, j, s: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, T, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(*operands)
