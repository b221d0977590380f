"""GEMM execution substrate: one dispatch layer for every model GEMM.

The paper's selection loop (core.planner / core.timing, Eqs. 6-7) picks a
pipeline-collapse depth k *per GEMM shape*; this module is the pipe that
makes those picks configure actual execution.  Every dense contraction in
nn/ and models/ routes through :func:`gemm` (or :func:`expert_gemm` for the
MoE batched form, :func:`batched_gemm` for attention QK/PV products), which

  * resolves the GEMM's :class:`GemmPlan` from a process-wide **plan
    cache** keyed on ``(M, N, T, backend, epilogue, shard)`` — the Eq.(6')
    argmin runs once per *post-partition* shape, not once per jit trace or
    serving request;
  * records the plan under the caller's **site label** (``attn.wq``,
    ``mlp.wo``, ``attn.qk``, ...), the same names
    ``core.planner.model_gemms`` emits, so analytic plans and executed
    kernels are the same objects (the substrate benchmark joins the two
    tables on these labels), and counts the dispatch in
    :data:`DISPATCH_COUNTS`;
  * dispatches to a **backend** from a pluggable registry:

      ``xla``            today's ``x @ w`` (the default; numerics unchanged),
      ``arrayflex``      the Pallas K-collapse kernel at the planned k,
      ``arrayflex_int8`` the same kernel on int8 weights + per-output-
                         channel fp32 scales (fp32 accumulation, dequant
                         at the carry-propagate boundary), planned with
                         the int8 datapath's Eq.(5) coefficients,
      ``arrayflex_w8a8`` int8 weights AND dynamically quantized int8
                         activations: each grid step quantizes its
                         activation tile in-kernel (per-tile fp32 scale)
                         and the MAC chain runs int8 x int8 -> int32,
                         planned with the w8a8 datapath's coefficients
                         plus the Eq.(5') activation-quantize boundary
                         term (``timing.W8A8TimingParams.d_actq_ps``),
      ``ref``            an fp32-everywhere oracle for equivalence tests.

**Int8 weight quantization** (the ``arrayflex_int8`` backend): dispatch
quantizes each weight once through a per-weight-identity memo
(:func:`quantize_weight` — symmetric per-output-channel int8, fp32
scales), so eager dispatch never re-quantizes a weight it has seen (the
bench gates that hit rate at 100%).  Dispatch under a jit trace sees
tracers, not weight identities: quantization is staged into the
compiled step (once per compilation, but re-executed by XLA per call) —
hoisting it out via pre-quantized parameter trees is the ROADMAP
follow-up.  The kernel accumulates raw int8 codes in fp32 and the
dequant multiply resolves at the carry-propagate store, priced into
Eq.(5') as one boundary op per contraction.  Because the int8 datapath's collapse stages are cheap
(``timing.IntTimingParams``), the Eq.(6') argmin lands on deeper k than
fp32 picks at the same shape — the plan cache keys on the backend name,
which carries the precision.  Attention QK/PV products dispatch their
*activation* operands (K/V are not weights), so ``batched_gemm`` under
the int8 backend falls back to the fp32 arrayflex kernel and plan;
``moe.router`` is quantization-exempt (:data:`QUANT_EXEMPT_SITES`) —
router logits feed a discrete top-k, where quantization noise would
change expert routing rather than add bounded output error.

**Epilogues**: ``gemm(..., epilogue="silu"|"gelu"|"swiglu", bias=...,
w2=..., residual=...)`` fuses bias add, activation, the dual-contraction
gated multiply (swiglu: ``silu(x@w [+bias]) * (x@w2 [+bias2])``), and the
sublayer residual join (``residual + f(x)``) into the arrayflex kernel's
carry-propagate store — no HBM round-trip between a GEMM and its
activation or residual add.  Unfused backends (xla/ref) apply the identical
math as a post-pass (``apply_epilogue``), so every backend computes the
same function and equivalence tests stay meaningful.  The epilogue's
vector ops are priced into Eq.(5')/(6') and can shift the planned k.

``ModelConfig.gemm_backend`` selects the backend model-wide and
``ModelConfig.pallas_interpret`` the Pallas interpret mode; callers
thread both through (see models/lm.py).
New backends (quantized, ...) register with :func:`register_backend`.

**Sharded SPMD dispatch**: every entry point accepts a :class:`ShardCtx`
(mesh + operand PartitionSpecs + contraction reduce axes, derived per
site by ``parallel.sharding.gemm_shard_ctx`` and friends).  The dispatch
then runs the backend inside ``jax.shard_map`` so each device executes
its *post-partition* per-shard GEMM through the planned kernel, and the
plan itself is computed on the per-shard (M, N, T) — under tensor/FSDP
partitioning that is the shape the array actually executes, so the
Eq.(6') k-selection stays correct for sharded runs.  A TP row-parallel
weight (``attn.wo``-style, contraction sharded over 'model') psums its
partial accumulators at the collapsed-block boundary, *before* the
epilogue, and the psum's combine tree is priced into Eq.(5') as boundary
ops (``ShardSig.reduce_ops``) — which can legitimately shift the argmin
toward deeper collapse.

Shape convention matches core.planner: a call ``gemm(x, w)`` with
``x: (..., K)`` and ``w: (K, N_out)`` is the planner GEMM
``X[T, M] = A[T, N] x B[N, M]`` with ``M = N_out`` (output columns),
``N = K`` (contraction), ``T = prod(leading dims)`` (streamed rows).
``GemmPlan`` keeps those *logical* values and records the post-partition
``M_shard/N_shard/T_shard`` plus the per-shard Eq.(4) ``cycles``.
"""
from __future__ import annotations

import contextvars
import dataclasses
import functools
import math
import os
import weakref
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import planner, timing
from repro.kernels import ops
from repro.kernels.arrayflex_gemm import apply_epilogue, prologue_phase


# ---------------------------------------------------------------------------
# epilogue spec (hashable: lives in the plan-cache key and in GemmPlan)

EPILOGUE_KINDS = ("none", "silu", "gelu", "swiglu")


@dataclass(frozen=True)
class Epilogue:
    """What is fused after the contraction, at the carry-propagate store.

    ``kind`` names the activation structure (``swiglu`` = silu-gated dual
    contraction, which requires the ``w2`` operand); ``bias``/``bias2``
    record whether bias vectors ride along.  Pure shape-level metadata —
    the actual arrays are per-call operands — so the spec is hashable and
    participates in the memoized Eq.(6') plan.
    """

    kind: str = "none"
    bias: bool = False
    bias2: bool = False
    # residual-add fused after the activation/gate at the same boundary
    # (the transformer sublayer ``x + f(x)`` — one more Eq.(5') vector op)
    residual: bool = False
    # rmsnorm-scale multiply fused as a *prologue*: the per-input-channel
    # norm gain rides the x tile into the array (x * g before the MACs),
    # so the pre-attention norm is no longer a separate elementwise pass.
    # Still one Eq.(5') boundary ALU on the period — the scale stage sits
    # at the tile boundary in front of the array, exactly where the W8A8
    # quantizer does.
    norm_scale: bool = False

    @property
    def dual(self) -> bool:
        return self.kind == "swiglu"

    @property
    def activation(self) -> str:
        return "silu" if self.kind == "swiglu" else self.kind

    @property
    def ops(self) -> int:
        """Fused vector ops at the collapsed-block boundary (Eq. 5' ``e``):
        one per activation, gate multiply, bias add, residual add, and
        prologue norm-scale multiply."""
        return ((self.activation != "none") + self.dual
                + self.bias + self.bias2 + self.residual
                + self.norm_scale)

    @property
    def contractions(self) -> int:
        return 2 if self.dual else 1


EPILOGUE_NONE = Epilogue()


@dataclass
class GemmCall:
    """Per-call execution context handed to backends (operand arrays are
    not part of the memoized plan)."""

    out_dtype: Any = None       # None -> operand dtype; else fp32-acc cast
    w2: Any = None              # second contraction (epilogue.dual)
    bias: Any = None            # (N_out,) fused bias
    bias2: Any = None           # (N_out,) fused bias on the w2 contraction
    # per-output-channel fp32 dequant scales of an int8-quantized w / w2
    # (set by the dispatch for quantizing backends; None = fp32 weights)
    w_scale: Any = None
    w2_scale: Any = None
    # (T, N_out) residual stream added after the epilogue (epilogue.residual)
    residual: Any = None
    # (K,) per-input-channel rmsnorm gain fused as a prologue x-tile scale
    norm_scale: Any = None
    interpret: Optional[bool] = None   # Pallas interpret override


# ---------------------------------------------------------------------------
# plan-key introspection metadata (audited by analysis.kernel_check)
#
# The Eq.(6') plan cache keys on exactly these _plan_gemm_cached params;
# every GemmCall / BackendInfo field must either be covered by that key or
# be declared plan-irrelevant below.  analysis.kernel_check fails (AF006)
# on any dataclass field missing from its declaration table — adding a
# field to GemmCall/BackendInfo without deciding its keying story here is
# a build error, not silent plan-cache aliasing.

PLAN_KEY_PARAMS = ("M", "N", "T", "backend", "epilogue", "shard")

# GemmCall field -> keying declaration.  "epilogue:<attr>" means the field's
# presence is forced by that Epilogue attribute (which IS in the key);
# "backend:<attr>" likewise via BackendInfo (the backend name is in the
# key and re-registration evicts cached plans); "operand:" means the field
# is pure per-call runtime data that cannot change the planned k.
CALL_FIELD_KEYING = {
    "out_dtype": "operand: output cast only — the planned k is blind to the "
                 "store dtype (datapath precision rides the backend name)",
    "w2": "epilogue:dual — w2 present iff kind=='swiglu' (_epilogue_spec "
          "enforces the iff)",
    "bias": "epilogue:bias",
    "bias2": "epilogue:bias2",
    "w_scale": "backend:quantize — scales present iff the keyed backend "
               "quantizes (dequant_ops priced from BackendInfo.quantize)",
    "w2_scale": "backend:quantize",
    "residual": "epilogue:residual — residual present iff the keyed "
                "Epilogue spec carries the fused residual add",
    "norm_scale": "epilogue:norm_scale — the prologue rmsnorm gain is "
                  "present iff the keyed Epilogue spec prices it",
    "interpret": "operand: Pallas interpret mode swaps the executor, never "
                 "the plan (identical math at the same k)",
}

# BackendInfo field -> how the plan key covers it.  All metadata is carried
# by the backend *name* in the key: register_backend evicts cached plans on
# (re-)registration, so a name whose metadata changed cannot serve stale k.
BACKEND_FIELD_KEYING = {
    "fn": "identity: the name resolves fn at dispatch; plans never embed it",
    "collapse": "keyed-by-name: read inside _plan_gemm_cached",
    "precision": "keyed-by-name: read inside _plan_gemm_cached",
    "quantize": "keyed-by-name: read inside _plan_gemm_cached (dequant_ops)",
    "act_quantize": "keyed-by-name: read inside _plan_gemm_cached "
                    "(actq_ops — the Eq.(5') quantize boundary term)",
}


# ---------------------------------------------------------------------------
# strict-audit mode: routing violations become runtime errors
#
# REPRO_STRICT_AUDIT=1 (env) or the strict_audit_scope context manager turns
# an unknown/empty dispatch site label into a RuntimeError at dispatch time
# ([AF007], the finding code analysis.jaxpr_audit reports for the same
# violation) — the engine's jit traces then fail loudly instead of logging
# a silent new DISPATCH_COUNTS key.

_STRICT_AUDIT: contextvars.ContextVar = contextvars.ContextVar(
    "repro_strict_audit", default=None)


def strict_audit_enabled() -> bool:
    """Contextvar wins when set; else the REPRO_STRICT_AUDIT env var."""
    v = _STRICT_AUDIT.get()
    if v is not None:
        return bool(v)
    return os.environ.get("REPRO_STRICT_AUDIT", "") not in ("", "0")


class strict_audit_scope:
    """``with strict_audit_scope(): ...`` — site-label violations raise."""

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self._token = None

    def __enter__(self):
        self._token = _STRICT_AUDIT.set(self.enabled)
        return self

    def __exit__(self, *exc):
        _STRICT_AUDIT.reset(self._token)
        return False


def _known_sites() -> frozenset:
    from repro.core.planner import site_registry
    return site_registry()


def check_dispatch_sites(counts: Optional[Dict[str, int]] = None) -> None:
    """Assert every recorded dispatch label is planner-known.

    The cheap DISPATCH_COUNTS <-> planner.model_gemms drift check: a
    dispatch under a site the planner does not know is an error, not a
    silent new dict key.  Call it next to ``clear_plan_cache`` in test
    utilities (and the engine does under strict audit)."""
    known = _known_sites()
    unknown = sorted(
        label
        for site in (counts if counts is not None else DISPATCH_COUNTS)
        for label in site.split("+") if label not in known)
    if unknown:
        raise RuntimeError(
            f"[AF007] dispatch site labels unknown to planner.model_gemms: "
            f"{unknown}; known sites: {sorted(known)}")


# ---------------------------------------------------------------------------
# weight quantization (the arrayflex_int8 backend's memoized prologue)

# site labels whose weights stay fp32 under a quantizing backend: the
# router's logits feed a discrete top-k — quantization noise there changes
# *which experts run* instead of adding bounded output error, which would
# break the backend-equivalence tolerance contract.
QUANT_EXEMPT_SITES = frozenset({"moe.router"})

# id(weight) -> (weakref-or-thunk, int8 codes, fp32 scales).  Keyed on the
# weight array's identity: model params are long-lived objects, so every
# dispatch after the first is a pure dict hit — the hot path never
# re-quantizes.  The weakref death callback evicts the entry, so a reused
# id can never serve a stale quantization (the `ref() is w` guard below
# covers interpreters whose GC defers callbacks).
_QUANT_CACHE: Dict[int, tuple] = {}
QUANT_CACHE_STATS = {"hits": 0, "misses": 0, "traced": 0}


def _quantize(w):
    """Symmetric per-output-channel int8: codes in [-127, 127], fp32
    scales over the contraction axis (-2), so ``codes * scale`` recovers
    the weight to within scale/2 per element."""
    w32 = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=-2)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(w32 / scale[..., None, :]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def quantize_weight(w):
    """(int8 codes, fp32 per-output-channel scales) for a weight array,
    memoized on the array's identity.

    A 2-D (K, N) weight quantizes per output column (scales (N,)); an
    expert bank (E, K, N) per (expert, column) (scales (E, N)).  Concrete
    arrays hit the memo (``hits``/``misses`` in
    :data:`QUANT_CACHE_STATS`); tracers (dispatch under a jit trace)
    quantize in-graph and count as ``traced`` — the trace itself is
    cached by jit, so that cost is per-compilation, not per-step.
    """
    if isinstance(w, jax.core.Tracer):
        QUANT_CACHE_STATS["traced"] += 1
        return _quantize(w)
    key = id(w)
    ent = _QUANT_CACHE.get(key)
    if ent is not None and ent[0]() is w:
        QUANT_CACHE_STATS["hits"] += 1
        return ent[1], ent[2]
    QUANT_CACHE_STATS["misses"] += 1
    q, s = _quantize(w)
    if isinstance(q, jax.core.Tracer):
        # concrete weight quantized under an ambient trace (make_jaxpr /
        # jit over a closure lifts even concrete-operand ops into the
        # trace): memoizing the traced codes would leak tracers into
        # later dispatches — treat it as the in-trace path instead
        QUANT_CACHE_STATS["misses"] -= 1
        QUANT_CACHE_STATS["traced"] += 1
        return q, s
    try:
        ref = weakref.ref(w, lambda _, k=key: _QUANT_CACHE.pop(k, None))
    except TypeError:       # array type without weakref support: pin it
        ref = functools.partial(lambda v: v, w)
    _QUANT_CACHE[key] = (ref, q, s)
    return q, s


@jax.tree_util.register_pytree_node_class
class QuantizedTensor:
    """A weight pre-quantized at load time: int8 ``codes`` + fp32
    per-output-channel ``scale`` (the :func:`_quantize` pair), packaged
    as one pytree leaf-pair so it rides param trees through jit/scan —
    the scan over stacked super-blocks slices codes and scale together.

    ``lm.prequantize_params`` builds these once from the compute-dtype
    cast of each weight; the dispatch (:func:`gemm` / :func:`expert_gemm`)
    unpacks them directly instead of staging an in-trace requantize (the
    AF008 finding).  ``astype`` is a no-op: layers cast weights to the
    compute dtype *before* dispatch, and that cast is already baked into
    the codes."""

    __slots__ = ("codes", "scale")

    def __init__(self, codes, scale):
        self.codes = codes
        self.scale = scale

    def tree_flatten(self):
        return (self.codes, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def shape(self):
        return self.codes.shape

    @property
    def ndim(self):
        return self.codes.ndim

    def astype(self, dtype):
        return self

    def __repr__(self):
        return (f"QuantizedTensor(codes={getattr(self.codes, 'shape', ())},"
                f" scale={getattr(self.scale, 'shape', ())})")


def prequantize(w) -> QuantizedTensor:
    """Eagerly quantize a weight into a :class:`QuantizedTensor`.

    Runs the same :func:`_quantize` the in-trace path stages (elementwise
    round/clip plus an exact max reduction), so eager codes are bitwise
    identical to what a compiled step would have recomputed — the
    pre-quantized tree changes *where* quantization runs, never its
    values."""
    q, s = _quantize(w)
    return QuantizedTensor(q, s)


def backend_quantizes(name: str) -> bool:
    """Whether the registered backend consumes int8 weights (and so a
    pre-quantized param tree applies to it)."""
    check_backend(name)
    return _BACKEND_INFO[name].quantize


def backend_act_quantizes(name: str) -> bool:
    """Whether the registered backend also quantizes activation tiles
    dynamically (the W8A8 datapath): its in-trace int8 activation casts
    are the priced Eq.(5') quantize boundary, not rogue re-quantization
    — the jaxpr auditor keys its AF003 classification on this."""
    check_backend(name)
    return _BACKEND_INFO[name].act_quantize


def quantize_cache_info() -> Dict[str, int]:
    """hits / misses / traced counters plus the memo's current size."""
    return dict(QUANT_CACHE_STATS, size=len(_QUANT_CACHE))


def clear_quant_cache():
    _QUANT_CACHE.clear()
    for k in QUANT_CACHE_STATS:
        QUANT_CACHE_STATS[k] = 0


# ---------------------------------------------------------------------------
# shard signature / context

@dataclass(frozen=True)
class ShardSig:
    """Post-partition signature of a sharded dispatch (plan-cache key part).

    ``rows``/``contraction``/``cols`` are the shard counts of the logical
    T / N / M dims; ``reduce_ops`` prices the contraction psum's combine
    tree (``ceil(log2(shards))`` boundary adds) into Eq.(5') — the reduce
    resolves at the collapsed-block boundary alongside the epilogue, so it
    rides the same ``d_epilogue_ps`` critical-path term.

    ``transfer_ops``/``transfer_cycles`` price a pipeline-stage boundary
    (the 'pod'-axis ``collective_permute`` of a GPipe stage) into the
    plan, the same way the TP psum already is: ``transfer_ops`` are
    boundary ALU stages on the period (the egress combine/packetize tree
    — k-independent, so they deepen the argmin exactly like epilogue
    ops), while ``transfer_cycles`` serialize the incoming activation's
    ICI ingress in front of the schedule at the array's clock (Eq. 6'' —
    paid at the k-collapsed period, so they SHALLOW the argmin).  A
    throughput-bound prefill stage prices the egress tree; a
    latency-bound decode stage sits behind the full ingress — which is
    how ``best_k`` legitimately differs per serving role at the same
    (M, N, T).
    """

    rows: int = 1
    contraction: int = 1
    cols: int = 1
    reduce_ops: int = 0
    transfer_ops: int = 0
    transfer_cycles: int = 0


SHARD_NONE = ShardSig()


def _spec_shards(mesh, entry) -> int:
    """Total shard count a PartitionSpec entry induces under ``mesh``
    (absent axes count 1 — ``sharding.mesh_axis_size`` is the single
    source of truth for that rule)."""
    from repro.parallel.sharding import mesh_axis_size
    if entry is None:
        return 1
    axes = (entry,) if isinstance(entry, str) else tuple(entry)
    n = 1
    for a in axes:
        n *= mesh_axis_size(mesh, a)
    return n


@dataclass(frozen=True)
class ShardCtx:
    """How one substrate dispatch runs under the SPMD mesh.

    ``x_spec``/``w_spec``/``out_spec`` are PartitionSpecs of the operands
    *as dispatched* (x already flattened to ``(T, K)`` for :func:`gemm`;
    the batched/expert entries keep their leading batch/expert dims).
    ``reduce_axes`` names mesh axes the contraction is sharded over: each
    device computes a partial GEMM and the psum applies at the
    collapsed-block boundary, before the epilogue.  Derivation from the
    ``parallel.sharding`` site rules lives in ``sharding.gemm_shard_ctx``
    / ``batched_shard_ctx`` / ``expert_shard_ctx``.

    ``transfer_ops``/``transfer_cycles`` carry a pipeline-stage boundary
    price into the :class:`ShardSig` (see there).  A **pricing-only**
    context (``mesh is None``, replicated specs — built by
    ``sharding.pricing_shard_ctx``) keys the plan with the transfer terms
    but executes the dispatch unsharded: the GPipe path already runs the
    whole step under one 'pod' shard_map, so the per-stage GEMM must not
    nest another.
    """

    mesh: Any
    x_spec: Any
    w_spec: Any
    out_spec: Any
    reduce_axes: Tuple[str, ...] = ()
    transfer_ops: int = 0
    transfer_cycles: int = 0

    def axis_shards(self, entry) -> int:
        return _spec_shards(self.mesh, entry)

    def signature(self) -> ShardSig:
        """ShardSig for the 2-D :func:`gemm` entry (the plan-cache key)."""
        r = _spec_shards(self.mesh, tuple(self.reduce_axes) or None)
        return ShardSig(
            rows=self.axis_shards(self.x_spec[0]),
            contraction=self.axis_shards(self.x_spec[1]),
            cols=self.axis_shards(self.w_spec[1]),
            reduce_ops=math.ceil(math.log2(r)) if r > 1 else 0,
            transfer_ops=self.transfer_ops,
            transfer_cycles=self.transfer_cycles)

    def divides(self, T: int, K: int, N_out: int) -> bool:
        s = self.signature()
        return (T % s.rows == 0 and K % s.contraction == 0
                and N_out % s.cols == 0)


@dataclass(frozen=True)
class GemmPlan:
    """One plan-cache entry: logical shape, epilogue, shard signature,
    chosen depth, and *per-shard* Eq.(6') predictions (ps)."""

    M: int              # output columns (logical, pre-partition)
    N: int              # contraction (logical)
    T: int              # streamed rows (logical)
    backend: str
    k: int              # collapse depth the kernel runs with (1 off-ArrayFlex)
    t_pred_ps: float    # per-shard Eq.(6') model time at k
    t_conventional_ps: float  # per-shard fixed-pipeline SA baseline
    epilogue: Epilogue = EPILOGUE_NONE
    shard: ShardSig = SHARD_NONE
    M_shard: int = 0    # post-partition shape each device executes
    N_shard: int = 0
    T_shard: int = 0
    cycles: int = 0     # per-shard Eq.(4) cycles x fused contractions
    precision: str = "fp32"   # datapath the Eq.(5)-(7) pricing used

    @property
    def saving(self) -> float:
        return 1.0 - self.t_pred_ps / self.t_conventional_ps


@functools.lru_cache(maxsize=None)
def _plan_gemm_cached(M: int, N: int, T: int, backend: str,
                      epilogue: Epilogue, shard: ShardSig) -> GemmPlan:
    info = _BACKEND_INFO.get(backend)
    collapse = info.collapse if info else False
    precision = info.precision if info else "fp32"
    params = timing.timing_for(precision)
    Ms = -(-M // shard.cols)
    Ns = -(-N // shard.contraction)
    Ts = -(-T // shard.rows)
    # a quantizing backend's per-output-channel dequant multiply resolves
    # at the carry-propagate boundary like any fused op: one per contraction
    dequant_ops = epilogue.contractions if (info and info.quantize) else 0
    # a W8A8 backend's per-tile activation quantizer (amax + scale +
    # round/clip) is one more boundary stage, priced with its own Eq.(5')
    # coefficient (d_actq_ps) rather than d_epilogue_ps
    actq_ops = 1 if (info and info.act_quantize) else 0
    # a pipeline-stage boundary prices like the TP psum: its egress tree
    # is boundary ALU ops on the period, its ingress serializes cycles
    e_ops = (epilogue.ops + shard.reduce_ops + shard.transfer_ops
             + dequant_ops)
    k = (ops.plan_collapse(Ms, Ns, Ts, epilogue_ops=e_ops,
                           precision=precision, actq_ops=actq_ops,
                           transfer_cycles=shard.transfer_cycles)
         if collapse else 1)
    return GemmPlan(
        M=M, N=N, T=T, backend=backend, k=k, epilogue=epilogue, shard=shard,
        M_shard=Ms, N_shard=Ns, T_shard=Ts, precision=precision,
        cycles=epilogue.contractions * timing.total_cycles(
            Ms, Ns, Ts, ops.SA_R, ops.SA_C, k),
        t_pred_ps=timing.t_abs_ps(Ms, Ns, Ts, ops.SA_R, ops.SA_C, k,
                                  params=params, epilogue_ops=e_ops,
                                  contractions=epilogue.contractions,
                                  actq_ops=actq_ops,
                                  extra_cycles=shard.transfer_cycles),
        t_conventional_ps=timing.t_abs_conventional_ps(
            Ms, Ns, Ts, ops.SA_R, ops.SA_C, params=params,
            contractions=epilogue.contractions,
            epilogue_ops=e_ops, actq_ops=actq_ops,
            extra_cycles=shard.transfer_cycles))


# backend name -> {"hits": n, "misses": n} of plan_gemm lookups: which
# backends are planning fresh shapes vs running cache-hit-only.  Steady-
# state serving must be all hits (see plan_cache_info / the serving test).
PLAN_CACHE_STATS: Dict[str, Dict[str, int]] = {}


def plan_gemm(M: int, N: int, T: int, backend: str = "arrayflex",
              epilogue: Epilogue = EPILOGUE_NONE,
              shard: ShardSig = SHARD_NONE) -> GemmPlan:
    """Plan-cache entry point: Eq.(6') argmin once per
    (M, N, T, backend, epilogue, shard).

    (M, N, T) are the *logical* dims; the argmin runs on the
    post-partition per-shard shape — the GEMM the array actually executes
    under the mesh — and a sharded contraction prices its psum combine
    tree into the boundary ops (see :class:`ShardSig`).  The backend name
    carries the datapath precision: a quantizing backend prices Eq.(5')
    with its own ``timing`` coefficients plus one dequant boundary op per
    contraction, so the same shape legitimately plans a different k under
    int8 than under fp32.  Lookups are tallied per backend in
    :data:`PLAN_CACHE_STATS`."""
    before = _plan_gemm_cached.cache_info().misses
    plan = _plan_gemm_cached(M, N, T, backend, epilogue, shard)
    st = PLAN_CACHE_STATS.setdefault(backend, {"hits": 0, "misses": 0})
    missed = _plan_gemm_cached.cache_info().misses > before
    st["misses" if missed else "hits"] += 1
    return plan


@dataclass(frozen=True)
class PlanCacheInfo:
    """Aggregate lru stats plus the per-backend hit/miss tallies and the
    ``planner.attention_plan`` memo counters (chunk/page geometry picks —
    the serving zero-miss guarantee covers them too)."""

    hits: int
    misses: int
    maxsize: Optional[int]
    currsize: int
    per_backend: Dict[str, Dict[str, int]] = field(default_factory=dict)
    attention_plan: Dict[str, int] = field(default_factory=dict)

    def _asdict(self):
        return dataclasses.asdict(self)


def plan_cache_info() -> PlanCacheInfo:
    info = _plan_gemm_cached.cache_info()
    ap = planner.attention_plan.cache_info()
    return PlanCacheInfo(
        hits=info.hits, misses=info.misses, maxsize=info.maxsize,
        currsize=info.currsize,
        per_backend={b: dict(st) for b, st in PLAN_CACHE_STATS.items()},
        attention_plan={"hits": ap.hits, "misses": ap.misses,
                        "currsize": ap.currsize})


def clear_plan_cache():
    """Reset every plan memo this process holds: the Eq.(6') plan cache
    (and its per-backend tallies) AND the planner memos it feeds from
    (``ops.plan_collapse``, ``planner.attention_plan``) — a
    timing-parameter or config change must not see stale picks — plus the
    per-trace site/dispatch logs.  The weight-quantization memo is NOT a
    plan and survives (``clear_quant_cache`` resets it)."""
    _plan_gemm_cached.cache_clear()
    PLAN_CACHE_STATS.clear()
    ops.plan_collapse.cache_clear()
    planner.attention_plan.cache_clear()
    SITE_PLANS.clear()
    DISPATCH_COUNTS.clear()


# ---------------------------------------------------------------------------
# backend registry

def _prescale(x2, norm_scale):
    """Unfused-backend form of the prologue rmsnorm-scale: the same
    ``prologue_phase`` expression the kernel inlines per tile, applied to
    the whole x — fused and unfused paths agree bit for bit."""
    if norm_scale is None:
        return x2
    return prologue_phase(x2, norm_scale)


def _xla_backend(x2, w, plan: GemmPlan, call: GemmCall):
    ep = plan.epilogue
    x2 = _prescale(x2, call.norm_scale)
    if call.out_dtype is None:
        # bit-for-bit the pre-substrate path: operand-dtype contraction(s),
        # epilogue applied in the same op order the unfused layers used
        # (residual + out matches the layers' ``x + f(x)``)
        y = x2 @ w
        y2 = x2 @ call.w2 if ep.dual else None
        out = apply_epilogue(y, y2, call.bias, call.bias2, ep.activation)
        return out if call.residual is None else call.residual + out
    y = jnp.dot(x2, w, preferred_element_type=jnp.float32)
    y2 = (jnp.dot(x2, call.w2, preferred_element_type=jnp.float32)
          if ep.dual else None)
    out = apply_epilogue(y, y2, call.bias, call.bias2, ep.activation)
    if call.residual is not None:
        out = call.residual.astype(jnp.float32) + out
    return out.astype(call.out_dtype)


def _arrayflex_backend(x2, w, plan: GemmPlan, call: GemmCall):
    return ops.arrayflex_matmul(x2, w, w2=call.w2, bias=call.bias,
                                bias2=call.bias2, residual=call.residual,
                                norm_scale=call.norm_scale,
                                activation=plan.epilogue.activation,
                                k_collapse=plan.k, out_dtype=call.out_dtype,
                                interpret=call.interpret)


def _ref_backend(x2, w, plan: GemmPlan, call: GemmCall):
    x32 = _prescale(x2, call.norm_scale).astype(jnp.float32)
    y = jnp.dot(x32, w.astype(jnp.float32))
    y2 = (jnp.dot(x32, call.w2.astype(jnp.float32))
          if plan.epilogue.dual else None)
    b = None if call.bias is None else call.bias.astype(jnp.float32)
    b2 = None if call.bias2 is None else call.bias2.astype(jnp.float32)
    out = apply_epilogue(y, y2, b, b2, plan.epilogue.activation)
    if call.residual is not None:
        out = call.residual.astype(jnp.float32) + out
    return out.astype(call.out_dtype or x2.dtype)


def _arrayflex_int8_backend(x2, w, plan: GemmPlan, call: GemmCall):
    # w arrives pre-quantized from the dispatch's weight memo: int8 codes
    # with call.w_scale the per-output-channel fp32 dequant (w2 likewise).
    # A quantization-exempt site (moe.router) passes fp32 w with no scale
    # and runs the fp32 kernel unchanged, under the fp32-priced plan the
    # dispatch substitutes for exempt sites.
    return ops.arrayflex_matmul(x2, w, w2=call.w2, bias=call.bias,
                                bias2=call.bias2, w_scale=call.w_scale,
                                w2_scale=call.w2_scale,
                                residual=call.residual,
                                norm_scale=call.norm_scale,
                                activation=plan.epilogue.activation,
                                k_collapse=plan.k, out_dtype=call.out_dtype,
                                interpret=call.interpret)


def _arrayflex_w8a8_backend(x2, w, plan: GemmPlan, call: GemmCall):
    # Same operand contract as the int8 backend (codes + scales from the
    # dispatch memo); ``act_quant`` keys on the scales' presence, so an
    # exempt site (fp32 w, no scale — planned as the fp32 base) runs the
    # fp32 kernel while every quantized site engages the in-kernel
    # per-tile activation quantizer and the int8 x int8 -> int32 chain.
    return ops.arrayflex_matmul(x2, w, w2=call.w2, bias=call.bias,
                                bias2=call.bias2, w_scale=call.w_scale,
                                w2_scale=call.w2_scale,
                                act_quant=call.w_scale is not None,
                                residual=call.residual,
                                norm_scale=call.norm_scale,
                                activation=plan.epilogue.activation,
                                k_collapse=plan.k, out_dtype=call.out_dtype,
                                interpret=call.interpret)


@dataclass(frozen=True)
class BackendInfo:
    """Registry metadata driving planning and dispatch for one backend.

    ``collapse``: plans an Eq.(6') collapse depth (ArrayFlex-family
    kernels); others run k=1.  ``precision``: the datapath whose
    ``timing`` coefficients price Eq.(5)-(7) for this backend (part of
    the plan, carried by the backend name in the cache key).
    ``quantize``: the dispatch pre-quantizes weight operands through
    :func:`quantize_weight` and hands int8 codes + scales to ``fn``.
    ``act_quantize``: the backend also quantizes activation tiles
    dynamically in-kernel (W8A8) — planning prices one Eq.(5')
    activation-quantize boundary op (``timing`` ``d_actq_ps``) on top of
    the dequant ops.  Requires ``quantize`` (the kernel's int8 chain
    needs int8 weight codes on the other operand).
    """

    fn: Callable
    collapse: bool = False
    precision: str = "fp32"
    quantize: bool = False
    act_quantize: bool = False


_BACKENDS: Dict[str, Callable] = {}
_BACKEND_INFO: Dict[str, BackendInfo] = {}


def register_backend(name: str, fn: Callable, *, collapse: bool = False,
                     precision: str = "fp32",
                     quantize: bool = False,
                     act_quantize: bool = False) -> None:
    """fn(x2: (T, K), w: (K, N_out), plan: GemmPlan, call: GemmCall)
    -> (T, N_out).  ``call`` carries out_dtype, the epilogue operands
    (w2/bias/bias2 — apply with ``kernels.arrayflex_gemm.apply_epilogue``
    if not fusing), the dequant scales of a quantizing backend
    (``call.w_scale is None`` on paths that do not quantize: exempt
    sites, batched activation products — the fn must handle fp32
    operands then), and the Pallas interpret override.  See
    :class:`BackendInfo` for the keyword metadata.

    (Re-)registration evicts cached Eq.(6') plans: a plan embeds the
    backend's collapse/precision metadata, so a name whose metadata
    changes must not keep serving stale k picks."""
    timing.timing_for(precision)     # fail fast on unknown precisions
    if act_quantize and not quantize:
        raise ValueError(
            f"backend {name!r}: act_quantize requires quantize — the W8A8 "
            f"int8 chain multiplies quantized activation tiles against "
            f"int8 weight codes")
    _BACKENDS[name] = fn
    _BACKEND_INFO[name] = BackendInfo(fn=fn, collapse=collapse,
                                      precision=precision,
                                      quantize=quantize,
                                      act_quantize=act_quantize)
    _plan_gemm_cached.cache_clear()
    PLAN_CACHE_STATS.clear()


def backends():
    return sorted(_BACKENDS)


def check_backend(name: str) -> None:
    """Validate a backend name against the registry (the config-resolve-
    time guard: ModelConfig.gemm_backend / serve.py --gemm-backend call
    this before any dispatch, so an unknown name fails with the
    registered list instead of deep inside a jit trace)."""
    if name not in _BACKENDS:
        raise ValueError(
            f"unknown gemm backend {name!r}; registered: {backends()}")


def get_backend(name: str) -> Callable:
    check_backend(name)
    return _BACKENDS[name]


register_backend("xla", _xla_backend)
register_backend("arrayflex", _arrayflex_backend, collapse=True)
register_backend("arrayflex_int8", _arrayflex_int8_backend, collapse=True,
                 precision="int8", quantize=True)
register_backend("arrayflex_w8a8", _arrayflex_w8a8_backend, collapse=True,
                 precision="w8a8", quantize=True, act_quantize=True)
register_backend("ref", _ref_backend)

_BUILTIN_BACKENDS = {"xla": _xla_backend, "arrayflex": _arrayflex_backend,
                     "arrayflex_int8": _arrayflex_int8_backend,
                     "arrayflex_w8a8": _arrayflex_w8a8_backend,
                     "ref": _ref_backend}

# builtin quantizing backend -> the fp32 ArrayFlex base that exempt sites
# and non-quantizable dispatches plan (and, on the batched path, execute)
# instead — the recorded Eq.(6') prediction must match the datapath the
# array actually runs.
_QUANT_FP32_BASE = {"arrayflex_int8": "arrayflex",
                    "arrayflex_w8a8": "arrayflex"}

# Batched (activation x activation) sites the W8A8 backend quantizes:
# attn.qk only.  Both QK operands quantize dynamically — K per key column
# in-trace (one scale per key position, via _quantize), q per tile in the
# kernel prologue — and the resulting logit error is bounded relative to
# |q||k|, which the softmax tolerates at the gated tolerances.  attn.pv
# stays on the fp32 base: softmax concentrates the probability operand's
# mass near zero, and symmetric per-tile int8 (resolution amax/127 with
# amax ~ 1) would zero exactly the long tail of small attention weights
# that distinguishes outputs.  Cross-attention QK keeps the conservative
# fp32 base until separately validated.
BATCHED_ACTQ_SITES = frozenset({"attn.qk"})


def _is_builtin(name: str) -> bool:
    """True when ``name`` still resolves to the built-in implementation —
    a re-registered override must win on the batched/expert fast paths
    exactly as it does in :func:`gemm`."""
    return _BACKENDS.get(name) is _BUILTIN_BACKENDS.get(name)


# site label -> GemmPlan of the most recent trace through that site.
# Populated at jit-trace time (shapes are static there), so one model
# forward leaves exactly its GEMM working set behind for inspection.
# A fused dual-GEMM site like "mlp.wi_gate+mlp.wi_up" records the shared
# plan under BOTH component labels.
SITE_PLANS: Dict[str, GemmPlan] = {}

# site label (as passed, fused labels kept joined) -> number of substrate
# dispatches traced through that site.  For the arrayflex backend one
# dispatch == one kernel launch, so this is the launch count the MoE
# batching and epilogue fusion reduce (3E -> 3, 2 GEMM launches -> 1).
DISPATCH_COUNTS: Dict[str, int] = {}


def _maybe_chaos_fault(site: str) -> None:
    """Chaos injection point ``substrate.dispatch``: fail this GEMM launch
    when the ambient :mod:`repro.runtime.chaos` engine says so (no-op —
    one contextvar read — when chaos is inactive).  Dispatch runs at
    jit-trace time, so a fault fires at the launch/trace boundary of a
    compiled step; failed traces are not cached, so a retry re-dispatches
    and draws again.  Imports are lazy: substrate must not import serving
    at module load (serving imports substrate)."""
    from repro.runtime import chaos
    if chaos.fire("substrate.dispatch", site):
        from repro.serving.errors import KernelFault
        raise KernelFault(
            f"[chaos] injected GEMM launch fault at site {site!r} "
            f"(replayable: seed + draw index in the chaos log)")


def _record(site: str, plan: GemmPlan, launches: int = 1) -> None:
    if not site:
        if strict_audit_enabled():
            raise RuntimeError(
                "[AF007] unlabeled substrate dispatch under strict audit: "
                "every model GEMM must carry a planner site label")
        return
    if strict_audit_enabled():
        known = _known_sites()
        bad = [label for label in site.split("+") if label not in known]
        if bad:
            raise RuntimeError(
                f"[AF007] dispatch site {site!r} carries labels unknown to "
                f"planner.model_gemms: {bad}")
    for label in site.split("+"):
        SITE_PLANS[label] = plan
    DISPATCH_COUNTS[site] = DISPATCH_COUNTS.get(site, 0) + launches


def _epilogue_spec(epilogue: str, w2, bias, bias2, residual=None,
                   norm_scale=None) -> Epilogue:
    if epilogue not in EPILOGUE_KINDS:
        raise ValueError(f"unknown epilogue {epilogue!r}; "
                         f"supported: {EPILOGUE_KINDS}")
    if (epilogue == "swiglu") != (w2 is not None):
        raise ValueError("epilogue='swiglu' requires w2 (and only swiglu "
                         "takes a second contraction)")
    if bias2 is not None and w2 is None:
        raise ValueError("bias2 requires the w2 contraction")
    return Epilogue(kind=epilogue, bias=bias is not None,
                    bias2=bias2 is not None,
                    residual=residual is not None,
                    norm_scale=norm_scale is not None)


# ---------------------------------------------------------------------------
# dispatch

def _sharded_gemm(fn, x2, w, plan: GemmPlan, ctx: ShardCtx, call: GemmCall):
    """Run one planned 2-D GEMM under ``jax.shard_map``: each device
    executes the post-partition per-shard GEMM through ``fn`` at the
    plan's k.  A sharded contraction (``ctx.reduce_axes``) psums the
    partial fp32 accumulators at the collapsed-block boundary and applies
    the epilogue *after* the reduce (a per-shard bias/activation on
    partial sums would be wrong).

    Int8 operands (a quantizing backend): the dequant scales are (N_out,)
    vectors and shard with the output-column axis exactly like fused
    biases — replicated for a row-parallel (contraction-sharded) weight,
    column-sharded for a column-parallel one.  On the reduce path each
    shard dequants its *partial* accumulator before the psum (per-column
    scales distribute over the K sum, so pre-psum dequant is exact) and
    the cross-device psum itself stays fp32."""
    ep = plan.epilogue
    reduce_axes = ctx.reduce_axes
    col_spec = P(ctx.w_spec[1])          # (N_out,) operands follow out cols
    operands, in_specs = [x2, w], [ctx.x_spec, ctx.w_spec]
    flags = []
    for arr, spec in ((call.w2, ctx.w_spec), (call.w_scale, col_spec),
                      (call.w2_scale, col_spec), (call.bias, col_spec),
                      (call.bias2, col_spec),
                      # the residual stream is output-shaped: shard like out
                      (call.residual, ctx.out_spec),
                      # the prologue norm scale is (K,): follows x's
                      # contraction axis, so each shard scales its x slice
                      (call.norm_scale, P(ctx.x_spec[1]))):
        flags.append(arr is not None)
        if arr is not None:
            operands.append(arr)
            in_specs.append(spec)
    has_w2, has_s, has_s2, has_b, has_b2, has_r, has_g = flags
    # reduce path: the per-shard kernel runs the contraction(s) only, at
    # the SAME k the (reduce-priced) plan picked
    exec_plan = (dataclasses.replace(plan, epilogue=EPILOGUE_NONE)
                 if reduce_axes else plan)

    def body(*ops_):
        it = iter(ops_)
        xs, ws = next(it), next(it)
        w2s = next(it) if has_w2 else None
        ss = next(it) if has_s else None
        s2s = next(it) if has_s2 else None
        bs = next(it) if has_b else None
        b2s = next(it) if has_b2 else None
        rs = next(it) if has_r else None
        gs = next(it) if has_g else None
        if not reduce_axes:
            return fn(xs, ws, plan,
                      GemmCall(out_dtype=call.out_dtype, w2=w2s, bias=bs,
                               bias2=b2s, w_scale=ss, w2_scale=s2s,
                               residual=rs, norm_scale=gs,
                               interpret=call.interpret))
        # per-shard prologue scale is exact under the reduce: the (K,)
        # scale slice multiplies exactly the x columns this shard contracts
        pc = GemmCall(out_dtype=jnp.float32, w_scale=ss, norm_scale=gs,
                      interpret=call.interpret)
        y = jax.lax.psum(fn(xs, ws, exec_plan, pc), reduce_axes)
        y2 = (jax.lax.psum(fn(xs, w2s, exec_plan,
                              dataclasses.replace(pc, w_scale=s2s)),
                           reduce_axes)
              if has_w2 else None)
        out = apply_epilogue(
            y, y2,
            None if bs is None else bs.astype(jnp.float32),
            None if b2s is None else b2s.astype(jnp.float32),
            ep.activation)
        if rs is not None:       # residual joins after the post-psum epilogue
            out = rs.astype(jnp.float32) + out
        return out.astype(call.out_dtype or xs.dtype)

    return jax.shard_map(body, mesh=ctx.mesh, in_specs=tuple(in_specs),
                         out_specs=ctx.out_spec, check_vma=False)(*operands)


def gemm(x, w, *, site: str = "", backend: str = "xla", out_dtype=None,
         epilogue: str = "none", w2=None, bias=None, bias2=None,
         residual=None, norm_scale=None, interpret=None,
         shard: Optional[ShardCtx] = None):
    """The substrate entry: x (..., K) @ w (K, N_out) -> (..., N_out).

    ``out_dtype=None`` returns the operands' dtype with the backend's
    native accumulation; passing a dtype requests fp32 accumulation cast
    to it (the unembed/logits contract).

    ``epilogue`` fuses post-GEMM work into the dispatch (one kernel launch
    on the arrayflex backend): ``"silu"``/``"gelu"`` apply the activation
    to ``x@w [+ bias]``; ``"swiglu"`` computes
    ``silu(x@w [+ bias]) * (x@w2 [+ bias2])`` — the dual-GEMM gated MLP in
    ONE launch.  ``residual`` (an output-shaped ``(..., N_out)`` array)
    fuses the transformer sublayer's ``residual + f(x)`` add after the
    activation/gate, at the same carry-propagate boundary — no extra HBM
    round-trip between a sublayer GEMM and its residual join.  A fused
    site label like ``"mlp.wi_gate+mlp.wi_up"`` records the shared plan
    under both component names.

    ``shard`` (a :class:`ShardCtx`) dispatches under the SPMD mesh: the
    plan is computed on the post-partition per-shard (M, N, T) — keyed in
    the plan cache by the shard signature — and each device runs its
    per-shard GEMM inside ``jax.shard_map`` (contraction shards psum at
    the collapsed-block boundary, then the epilogue applies).  A shard
    context whose counts do not divide the dims (or an empty operand)
    falls back to replicated dispatch.

    On a quantizing backend (``arrayflex_int8`` / ``arrayflex_w8a8``) the
    dispatch swaps ``w`` (and ``w2``) for int8 codes + per-output-channel
    fp32 scales through the weight memo (:func:`quantize_weight`) before
    planning/sharding — unless the site is quantization-exempt
    (:data:`QUANT_EXEMPT_SITES`).
    """
    fn = get_backend(backend)
    _maybe_chaos_fault(site)
    info = _BACKEND_INFO[backend]
    if norm_scale is not None and norm_scale.shape != (x.shape[-1],):
        raise ValueError(
            f"site {site!r}: norm_scale shape {norm_scale.shape} must be "
            f"(K,) = ({x.shape[-1]},) — it scales x's contraction axis")
    ep = _epilogue_spec(epilogue, w2, bias, bias2, residual, norm_scale)
    w_scale = w2_scale = None
    plan_backend = backend
    if isinstance(w, QuantizedTensor):
        # load-time pre-quantized weight (lm.prequantize_params): unpack
        # codes + scales directly — no in-trace requantize to stage
        if not info.quantize:
            raise ValueError(
                f"site {site!r}: pre-quantized weight dispatched on "
                f"non-quantizing backend {backend!r}")
        if site in QUANT_EXEMPT_SITES:
            raise ValueError(
                f"site {site!r} is quantization-exempt but received a "
                f"pre-quantized weight")
        w, w_scale = w.codes, w.scale
        if isinstance(w2, QuantizedTensor):
            w2, w2_scale = w2.codes, w2.scale
    elif info.quantize and site in QUANT_EXEMPT_SITES:
        # an exempt site runs fp32 weights with no dequant (the w8a8
        # kernel's activation quantizer keys off the scales and stays off
        # too): price (and record) it as the fp32 base so its Eq.(6')
        # prediction matches the datapath it actually executes
        plan_backend = _QUANT_FP32_BASE.get(backend, plan_backend)
    elif info.quantize and w.shape[0] and w.shape[-1]:
        w, w_scale = quantize_weight(w)
        if w2 is not None:
            w2, w2_scale = quantize_weight(w2)
    lead = x.shape[:-1]
    K = x.shape[-1]
    N_out = w.shape[-1]
    x2 = x.reshape(math.prod(lead), K)   # explicit rows: K may be 0
    T = x2.shape[0]
    r2 = (None if residual is None
          else residual.reshape(T, N_out))   # raises on shape mismatch
    if shard is not None and (T * K * N_out == 0
                              or not shard.divides(T, K, N_out)):
        shard = None
    call = GemmCall(out_dtype=out_dtype, w2=w2, bias=bias, bias2=bias2,
                    w_scale=w_scale, w2_scale=w2_scale, residual=r2,
                    norm_scale=norm_scale, interpret=interpret)
    if shard is not None:
        plan = plan_gemm(N_out, K, T, plan_backend, ep, shard.signature())
        _record(site, plan)
        # pricing-only context (mesh=None): the plan is keyed/priced with
        # the role's transfer terms but the dispatch itself is unsharded —
        # pipeline-stage transfer cost is paid by the ppermute, not here
        out = (fn(x2, w, plan, call) if shard.mesh is None
               else _sharded_gemm(fn, x2, w, plan, shard, call))
    else:
        plan = plan_gemm(N_out, K, T, plan_backend, ep)
        _record(site, plan)
        out = fn(x2, w, plan, call)
    return out.reshape(*lead, N_out)


def _batched_exec(x, w, plan: GemmPlan, backend: str, out_dtype, interpret):
    """Builtin batched execution (B, T, K) @ (B, K, N): ONE launch."""
    if backend == "arrayflex":
        return ops.arrayflex_expert_matmul(x, w, k_collapse=plan.k,
                                           out_dtype=out_dtype,
                                           interpret=interpret)
    if backend == "arrayflex_w8a8":
        # W8A8 QK: both operands are activations, and both quantize
        # dynamically — the "w" operand (K^T) per (batch, column) in-trace,
        # one scale per key position, and each q tile in the kernel
        # prologue.  The int8 x int8 -> int32 chain runs exactly as on
        # weight GEMMs; the per-key scales dequant at the store.
        qw, ws = _quantize(w)
        return ops.arrayflex_expert_matmul(x, qw, w_scale=ws,
                                           act_quant=True,
                                           k_collapse=plan.k,
                                           out_dtype=out_dtype,
                                           interpret=interpret)
    if backend == "ref":
        out = jnp.matmul(x.astype(jnp.float32), w.astype(jnp.float32))
        return out.astype(out_dtype or x.dtype)
    if out_dtype is None:
        return jnp.matmul(x, w)
    return jnp.matmul(
        x, w, preferred_element_type=jnp.float32).astype(out_dtype)


def batched_gemm(x, w, *, site: str = "", backend: str = "xla",
                 out_dtype=None, interpret=None,
                 shard: Optional[ShardCtx] = None):
    """Batched GEMM: x (B, T, K) @ w (B, K, N) -> (B, T, N).

    The substrate path for attention QK/PV products (``attn.qk`` /
    ``attn.pv`` sites): every batch element runs the same planned shape,
    and the arrayflex backend executes ALL of them in one expert-batched
    kernel launch (batch = the leading grid dimension).  ``out_dtype``
    follows the :func:`gemm` contract (None -> operand dtype; a dtype ->
    fp32 accumulation cast once).

    ``shard`` (3-dim specs) splits the batch dim over mesh axes under
    ``jax.shard_map`` — each device runs ONE launch over its batch slice.
    Batch sharding leaves the per-element (M, N, T) unchanged, so the plan
    key does not change.  Custom backends and indivisible batches fall
    back to replicated dispatch.

    The batched operands are attention K/V *activations*, not weights —
    there is nothing to quantize once (weights-only quantization) — so
    the builtin ``arrayflex_int8`` backend maps to its fp32 ArrayFlex
    base (kernel AND plan), and a custom quantizing backend dispatches
    itself with ``call.w_scale=None`` (fp32 operands, the registry
    contract).  The ``arrayflex_w8a8`` backend *can* quantize an
    activation product — both operands dynamically — and does so on the
    sites in :data:`BATCHED_ACTQ_SITES` (``attn.qk``; PV stays on the
    fp32 base — see the constant's rationale), planned and recorded under
    the w8a8 datapath with the quantize boundary term priced.
    """
    check_backend(backend)
    _maybe_chaos_fault(site)
    if backend in _QUANT_FP32_BASE and not (
            _BACKEND_INFO[backend].act_quantize and _is_builtin(backend)
            and site in BATCHED_ACTQ_SITES):
        backend = _QUANT_FP32_BASE[backend]
    B, T, K = x.shape
    N_out = w.shape[-1]
    plan = plan_gemm(N_out, K, T, backend)
    if shard is not None and (not _is_builtin(backend)
                              or B % shard.axis_shards(shard.x_spec[0])):
        shard = None
    if shard is not None:
        _record(site, plan)

        def body(xs, ws):
            return _batched_exec(xs, ws, plan, backend, out_dtype, interpret)

        return jax.shard_map(body, mesh=shard.mesh,
                             in_specs=(shard.x_spec, shard.w_spec),
                             out_specs=shard.out_spec, check_vma=False)(x, w)
    if _is_builtin(backend):
        _record(site, plan)
        return _batched_exec(x, w, plan, backend, out_dtype, interpret)
    # custom backend: unroll the (static) batch through the 2-D entry —
    # B launches, each recorded against the shared per-shape plan
    _record(site, plan, launches=B)
    fn = get_backend(backend)
    call = GemmCall(out_dtype=out_dtype, interpret=interpret)
    return jnp.stack([fn(x[b], w[b], plan, call) for b in range(B)])


def _expert_exec(x, w, plan: GemmPlan, backend: str, interpret,
                 w_scale=None, act_quant: bool = False):
    """Builtin expert execution (G, E, C, K) @ (E, K, N): ONE launch.
    ``w_scale`` (E, N): int8 expert bank, dequantized per expert at the
    kernel's carry-propagate store.  ``act_quant`` (W8A8): the kernel
    additionally quantizes each activation tile in its prologue and runs
    the int8 x int8 -> int32 chain."""
    if backend == "xla":
        return jnp.einsum("gecd,edf->gecf", x, w)
    if backend == "ref":
        out = jnp.einsum("gecd,edf->gecf", x.astype(jnp.float32),
                         w.astype(jnp.float32))
        return out.astype(x.dtype)
    G, E, C, K = x.shape
    N_out = w.shape[-1]
    xe = x.transpose(1, 0, 2, 3).reshape(E, G * C, K)
    out = ops.arrayflex_expert_matmul(xe, w, w_scale=w_scale,
                                      act_quant=act_quant,
                                      k_collapse=plan.k,
                                      interpret=interpret)
    return out.reshape(E, G, C, N_out).transpose(1, 0, 2, 3)


def expert_gemm(x, w, *, site: str = "", backend: str = "xla",
                interpret=None, shard: Optional[ShardCtx] = None):
    """Batched expert GEMM: x (G, E, C, K) @ w (E, K, N) -> (G, E, C, N).

    Every backend plans ONE consistent (M=N, N=K, T=G*C) shape per site —
    the per-expert GEMMs of a capacity-buffered MoE layer are identical,
    so one plan covers all E of them.  The xla backend keeps the einsum
    the MoE layer always used (one fused batched contraction); the
    arrayflex backend folds the dispatch groups into the row dim and runs
    ALL experts in ONE kernel launch whose leading grid dimension is the
    expert axis (per-site launch count: 1, was E).

    ``shard`` (from ``sharding.expert_shard_ctx``) runs expert-parallel:
    the expert axis splits over 'model' under ``jax.shard_map`` and each
    device launches once over its E/tp experts (per-expert shape — and so
    the plan — unchanged).  Custom backends and indivisible expert counts
    fall back to replicated dispatch.

    A quantizing backend swaps the expert bank for int8 codes + (E, N)
    scales through the weight memo; the scales shard with the expert
    axis, exactly as the bank does.
    """
    check_backend(backend)
    _maybe_chaos_fault(site)
    G, E, C, K = x.shape
    N_out = w.shape[-1]
    info = _BACKEND_INFO[backend]
    w_scale = None
    if isinstance(w, QuantizedTensor):
        if not info.quantize:
            raise ValueError(
                f"site {site!r}: pre-quantized expert bank dispatched on "
                f"non-quantizing backend {backend!r}")
        w, w_scale = w.codes, w.scale
    elif info.quantize and E and K and N_out:
        w, w_scale = quantize_weight(w)
    # W8A8: the expert kernel engages its in-kernel activation quantizer
    # whenever the bank is quantized (the plan priced the boundary term)
    actq = bool(info.act_quantize and w_scale is not None)
    plan = plan_gemm(N_out, K, G * C, backend)
    if shard is not None and (not _is_builtin(backend)
                              or E % shard.axis_shards(shard.x_spec[1])):
        shard = None
    if shard is not None:
        _record(site, plan)

        if w_scale is not None:
            def body_q(xs, ws, ss):
                return _expert_exec(xs, ws, plan, backend, interpret, ss,
                                    actq)

            return jax.shard_map(
                body_q, mesh=shard.mesh,
                in_specs=(shard.x_spec, shard.w_spec,
                          P(shard.w_spec[0], None)),
                out_specs=shard.out_spec, check_vma=False)(x, w, w_scale)

        def body(xs, ws):
            return _expert_exec(xs, ws, plan, backend, interpret)

        return jax.shard_map(body, mesh=shard.mesh,
                             in_specs=(shard.x_spec, shard.w_spec),
                             out_specs=shard.out_spec, check_vma=False)(x, w)
    if _is_builtin(backend):
        _record(site, plan)
        return _expert_exec(x, w, plan, backend, interpret, w_scale, actq)
    # custom backend: unroll the (static) expert axis through the 2-D
    # entry — E launches, each recorded against the shared per-shape plan
    # (a quantizing backend's per-expert dequant scales ride along)
    _record(site, plan, launches=E)
    fn = get_backend(backend)
    outs = [fn(x[:, e].reshape(G * C, K), w[e], plan,
               GemmCall(interpret=interpret,
                        w_scale=None if w_scale is None else w_scale[e])
               ).reshape(G, C, N_out)
            for e in range(E)]
    return jnp.stack(outs, axis=1)
