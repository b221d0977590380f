"""W8A8 ArrayFlex backend: dynamic per-tile activation quantization.

Covers the quantizer itself (property tests over ``quantize_tile``), the
int8 x int8 -> int32 kernel MAC path (jaxpr acceptance assertion), the
Eq.(5') activation-quantize boundary term and its k-shift, exempt-site
routing, and the model-level equivalence matrix
w8a8 x {dense, MoE, Mamba} x {unsharded, TP2}.

Tolerance contract (documented here and in docs/substrate.md):

* quantizer level — ``quantize_tile`` round-trips with per-element error
  <= ``scale / 2 = amax / 254``; an all-zero tile yields all-zero codes
  (zero K-padding tails contribute exactly 0 to the accumulator).
* kernel level, single-tile shapes — when the whole operand fits one
  (bm, bk) grid tile the in-kernel quantizer sees exactly the full
  operand, so the w8a8 dispatch must equal the fake-quantized fp32
  oracle — per-tile-quantized activation against per-output-channel
  quantized weight — to fp32 accumulation tolerance
  (atol 1e-4): the kernel adds NO error beyond quantization.
* model level vs the fp32 arrayflex backend — per-tile activation
  rounding adds ~0.4% relative error per GEMM on top of the W8 weight
  error; on the reduced fp32 configs: dense/Mamba ``atol=0.12``
  (observed ~0.031 on logit scale ~0.55).  The MoE family amplifies it
  through router top-k flips on near-tie tokens exactly as under W8:
  ``atol=2.5`` (observed ~1.13 on logit scale ~3.0).
* sharded (TP2) w8a8 vs unsharded w8a8 — NOT bit-exact, unlike W8: a
  row-parallel shard re-tiles the contraction, so the per-tile
  activation scales differ from the unsharded tiling.  The discrepancy
  is quantization-noise sized and bounded by the same family tolerances
  (observed ~0.022 dense / ~0.026 Mamba / ~1.14 MoE).
* greedy streams — bit-identical run-to-run per backend.  Against the
  fp32 stream the comparison is on logits (teacher-forced along the
  w8a8 stream, dense ``atol``): a random-weight top-1 margin can be
  smaller than the quantization perturbation, so tokens may differ.
"""
import dataclasses
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import ARCHS, reduced
from repro.core import planner, timing
from repro.kernels import ops, substrate
from repro.kernels.arrayflex_gemm import quantize_tile
from repro.models import lm
from repro.serving import ServeConfig, ServingEngine
from repro.serving.engine import Request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

needs8 = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs 8 host devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")

# model-level w8a8-vs-fp32 tolerance per family (see module docstring)
ATOL = {"qwen2-0.5b": 0.12, "mamba2-370m": 0.12, "qwen3-moe-30b-a3b": 2.5}


def _cfg(arch, backend="xla", mesh=()):
    return reduced(ARCHS[arch], compute_dtype="float32",
                   param_dtype="float32", gemm_backend=backend,
                   mesh_shape=mesh)


_PARAMS = {}


def _params(arch):
    if arch not in _PARAMS:
        _PARAMS[arch] = lm.init_params(_cfg(arch), jax.random.PRNGKey(0))
    return _PARAMS[arch]


_TOKS = np.random.RandomState(0).randint(2, 512, (2, 16))


def _fake_quant(a):
    """Per-tile fake-quant of an activation (quantize_tile semantics)."""
    q, s = quantize_tile(jnp.asarray(a, jnp.float32))
    return q.astype(jnp.float32) * s


def _dequant_w(w):
    """Per-output-channel fake-quant of a weight (quantize_weight
    semantics — the weight side of W8A8 is identical to W8)."""
    q, s = substrate._quantize(w)
    return q.astype(jnp.float32) * s[..., None, :]


# ----------------------------------------------------------- registration
def test_w8a8_backend_registered_with_metadata():
    assert "arrayflex_w8a8" in substrate.backends()
    info = substrate._BACKEND_INFO["arrayflex_w8a8"]
    assert info.collapse and info.quantize and info.act_quantize
    assert info.precision == "w8a8"
    # W8 quantizes weights only; its activations stay fp32
    assert not substrate._BACKEND_INFO["arrayflex_int8"].act_quantize
    assert substrate.backend_act_quantizes("arrayflex_w8a8")
    assert not substrate.backend_act_quantizes("arrayflex_int8")


def test_register_act_quantize_requires_quantize():
    """An activation-only int8 path has no dequant-scale story — the
    registry must reject the inconsistent capability combination."""
    with pytest.raises(ValueError, match="act_quantize requires quantize"):
        substrate.register_backend("_a8", lambda *a: None,
                                   precision="int8", act_quantize=True)
    assert "_a8" not in substrate.backends()


# ------------------------------------------- quantizer property tests
@settings(max_examples=60, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       rows=st.sampled_from([1, 3, 8, 128]),
       cols=st.sampled_from([1, 7, 128]),
       log_mag=st.floats(-6.0, 6.0))
def test_quantize_tile_round_trip_bound(seed, rows, cols, log_mag):
    """codes * scale reproduces the tile within scale/2 = amax/254 per
    element, across magnitudes spanning twelve decades."""
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(rows, cols) * 10.0 ** log_mag, jnp.float32)
    codes, scale = quantize_tile(x)
    assert codes.dtype == jnp.int8
    assert int(jnp.max(jnp.abs(codes))) <= 127
    amax = float(jnp.max(jnp.abs(x)))
    assert float(scale) == pytest.approx(max(amax, 1e-12) / 127.0, rel=1e-6)
    err = np.abs(np.float32(codes) * float(scale) - np.float32(x))
    assert float(err.max()) <= float(scale) / 2 + 1e-12 * amax


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       rows=st.sampled_from([1, 5, 8]),
       cols=st.sampled_from([3, 7, 100]),
       pad_to=st.sampled_from([8, 128]))
def test_quantize_tile_zero_and_ragged_tail(seed, rows, cols, pad_to):
    """An all-zero tile quantizes to all-zero codes (finite scale, no
    NaN), and a zero-padded ragged tail neither changes the tile's scale
    nor contributes nonzero codes — K-padding is exact through the
    quantizer, so the padded accumulator matches the unpadded one."""
    zc, zs = quantize_tile(jnp.zeros((rows, cols), jnp.float32))
    assert float(zs) > 0 and not np.isnan(float(zs))
    assert int(jnp.max(jnp.abs(zc))) == 0
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(rows, cols), jnp.float32)
    xp = jnp.pad(x, ((0, 0), (0, max(pad_to - cols, 0))))
    c, s = quantize_tile(x)
    cp, sp = quantize_tile(xp)
    assert float(s) == float(sp)
    np.testing.assert_array_equal(np.asarray(cp[:, :cols]), np.asarray(c))
    assert int(jnp.max(jnp.abs(cp[:, cols:]))) == 0 if pad_to > cols else True


@settings(max_examples=20, deadline=None)
@given(kk=st.sampled_from([128, 256, 512]), sign=st.booleans())
def test_int32_accumulator_no_overflow_at_max_tile(kk, sign):
    """Worst-case int8 x int8 dot at the largest contraction tile the
    kernel ever runs (bk <= 512): |acc| <= kk * 127^2 ~= 8.3e6, five
    orders below the int32 ceiling — the per-step accumulator cannot
    wrap, so deferring the scale fold to fp32 is exact."""
    v = (-127 if sign else 127) * jnp.ones((1, kk), jnp.int8)
    w = 127 * jnp.ones((kk, 1), jnp.int8)
    acc = jnp.dot(v, w, preferred_element_type=jnp.int32)
    assert acc.dtype == jnp.int32
    assert int(acc[0, 0]) == (-1 if sign else 1) * kk * 127 * 127
    assert kk * 127 * 127 < np.iinfo(np.int32).max // 256


# -------------------------------------------- kernel-level exactness
@pytest.mark.parametrize("epilogue,bias", [
    ("none", False), ("silu", True), ("swiglu", True),
])
def test_w8a8_single_tile_matches_fake_quant_oracle(epilogue, bias):
    """Single-tile shapes: the in-kernel quantizer sees the whole
    operand, so w8a8 == fake-quantized fp32 oracle exactly (atol 1e-4) —
    the kernel's int8 MAC + deferred scale fold adds no error beyond
    quantization."""
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(8, 64), jnp.float32)
    w = jnp.asarray(rng.randn(64, 32), jnp.float32)
    w2 = jnp.asarray(rng.randn(64, 32), jnp.float32) \
        if epilogue == "swiglu" else None
    b = jnp.asarray(rng.randn(32), jnp.float32) if bias else None
    got = substrate.gemm(x, w, backend="arrayflex_w8a8", epilogue=epilogue,
                         w2=w2, bias=b)
    want = substrate.gemm(_fake_quant(x), _dequant_w(w), backend="xla",
                          epilogue=epilogue,
                          w2=None if w2 is None else _dequant_w(w2), bias=b)
    np.testing.assert_allclose(np.float32(got), np.float32(want),
                               rtol=1e-4, atol=1e-4)


def test_w8a8_multi_tile_tracks_fp32():
    """Ragged multi-tile shapes: per-tile scales differ from the global
    scale, so there is no closed-form oracle — bound the relative error
    against fp32 at the combined W8+A8 noise level instead."""
    rng = np.random.RandomState(8)
    x = jnp.asarray(rng.randn(300, 200), jnp.float32)
    w = jnp.asarray(rng.randn(200, 260), jnp.float32)
    got = substrate.gemm(x, w, backend="arrayflex_w8a8")
    want = substrate.gemm(x, w, backend="xla")
    rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert rel < 0.05, rel
    # residual fuses through the same store (satellite: fused sublayer add)
    r = jnp.asarray(rng.randn(300, 260), jnp.float32)
    got_r = substrate.gemm(x, w, backend="arrayflex_w8a8", residual=r)
    np.testing.assert_allclose(np.float32(got_r), np.float32(got + r),
                               rtol=1e-5, atol=1e-5)


def test_w8a8_expert_gemm_tracks_reference():
    rng = np.random.RandomState(9)
    x = jnp.asarray(rng.randn(2, 3, 5, 16), jnp.float32)     # (G,E,C,K)
    w = jnp.asarray(rng.randn(3, 16, 24), jnp.float32)       # (E,K,N)
    got = substrate.expert_gemm(x, w, backend="arrayflex_w8a8")
    want = jnp.einsum("gecd,edf->gecf", x, w)
    rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert rel < 0.05, rel


# ----------------------------- acceptance: the int8 MAC path engages
def _int8_dot_count(closed):
    n = 0
    from repro.analysis.jaxpr_audit import iter_eqns
    for eqn in iter_eqns(closed.jaxpr):
        if eqn.primitive.name != "dot_general":
            continue
        dts = {str(v.aval.dtype) for v in eqn.invars}
        if dts == {"int8"} and str(eqn.outvars[0].aval.dtype) == "int32":
            n += 1
    return n


def test_w8a8_kernel_stages_int8_int8_int32_dot():
    """Acceptance: the traced w8a8 dispatch carries dot_general equations
    with BOTH operands int8 and an int32 result — the int8 x int8 MAC
    path actually engages in-kernel.  Neither the fp32 nor the
    weight-only W8 backend stages any (W8 dequants the weight before its
    fp32 dot)."""
    x = jnp.ones((8, 256), jnp.float32)
    w = jnp.ones((256, 32), jnp.float32)

    def n_dots(backend):
        closed = jax.make_jaxpr(
            lambda a, b: substrate.gemm(a, b, backend=backend))(x, w)
        return _int8_dot_count(closed)

    assert n_dots("arrayflex_w8a8") >= 1
    assert n_dots("arrayflex") == 0
    assert n_dots("arrayflex_int8") == 0


# ------------------------------------------- w8a8-aware planning
def test_w8a8_timing_params():
    tp = timing.W8A8_TIMING
    assert timing.timing_for("w8a8") is tp
    assert tp.d_actq_ps > 0
    # the quantize boundary term prices per-step: period grows with it
    assert tp.clock_period_ps(2, actq_ops=1) > tp.clock_period_ps(2)
    # fp32/int8 datapaths never charge it
    assert timing.DEFAULT_TIMING.d_actq_ps == 0
    assert timing.INT8_TIMING.d_actq_ps == 0


def test_actq_term_shifts_best_k_at_model_shape():
    """Acceptance: the pinned decode GEMM (M, N, T) = (896, 4864, 512)
    plans k=2 on the w8a8 datapath with the quantizer UNpriced, and k=4
    with the Eq.(5') actq term priced — the activation-quantize boundary
    stage itself tips the argmin toward deeper collapse."""
    M, N, T = 896, 4864, 512
    assert ops.plan_collapse(M, N, T) == 2                       # fp32
    assert ops.plan_collapse(M, N, T, precision="w8a8") == 2     # no actq
    assert ops.plan_collapse(M, N, T, precision="w8a8",
                             actq_ops=1) == 4                    # actq priced
    p = substrate.plan_gemm(M, N, T, "arrayflex_w8a8")
    pf = substrate.plan_gemm(M, N, T, "arrayflex")
    assert (pf.k, p.k) == (2, 4)
    assert p.precision == "w8a8" and p.t_pred_ps < pf.t_pred_ps


def test_plan_prices_actq_and_dequant_together():
    """The cached plan charges BOTH the dequant boundary multiply
    (epilogue_ops) and the activation-quantize stage (actq_ops)."""
    p = substrate.plan_gemm(256, 128, 64, "arrayflex_w8a8")
    want = timing.t_abs_ps(256, 128, 64, ops.SA_R, ops.SA_C, p.k,
                           params=timing.W8A8_TIMING, epilogue_ops=1,
                           actq_ops=1)
    assert p.t_pred_ps == want
    # analytic planner table agrees
    g = planner.GEMM("mlp.wo", 256, 128, 64)
    lp = planner.plan_gemm_precision(g, 128, 128, "w8a8")
    assert lp.t_abs_ps == p.t_pred_ps and lp.k == p.k


def test_precision_table_three_way():
    rows = planner.precision_table(_cfg("qwen2-0.5b"),
                                   planner.ShapeConfig("t", 8, 2, "train"))
    assert rows
    assert all({"fp32", "int8", "w8a8"} <= set(r["plans"]) for r in rows)
    # the w8a8 datapath beats fp32 at every site despite the actq stage
    assert all(r["plans"]["w8a8"].t_abs_ps < r["plans"]["fp32"].t_abs_ps
               for r in rows)


# ------------------------------------------- exempt-site routing
def test_w8a8_exempt_and_actq_sites():
    """moe.router stays on the fp32 arrayflex base (bit-for-bit); the
    batched attn.qk quantizes (both operands are activations) while
    attn.pv stays exempt (softmax probability mass would be crushed by
    symmetric per-tile int8)."""
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(6, 8), jnp.float32)
    w = jnp.asarray(rng.randn(8, 4), jnp.float32)
    substrate.clear_plan_cache()
    got = substrate.gemm(x, w, site="moe.router", backend="arrayflex_w8a8")
    want = substrate.gemm(x, w, site="moe.router", backend="arrayflex")
    np.testing.assert_allclose(np.float32(got), np.float32(want),
                               rtol=1e-6, atol=1e-6)
    assert substrate.SITE_PLANS["moe.router"].precision == "fp32"
    assert "attn.qk" in substrate.BATCHED_ACTQ_SITES
    assert "attn.pv" not in substrate.BATCHED_ACTQ_SITES
    q = jnp.asarray(rng.randn(2, 16, 32), jnp.float32)
    kT = jnp.asarray(rng.randn(2, 32, 16), jnp.float32)
    substrate.clear_plan_cache()
    qk = substrate.batched_gemm(q, kT, site="attn.qk",
                                backend="arrayflex_w8a8")
    assert substrate.SITE_PLANS["attn.qk"].precision == "w8a8"
    ref = substrate.batched_gemm(q, kT, site="attn.qk", backend="xla")
    rel = float(jnp.linalg.norm(qk - ref) / jnp.linalg.norm(ref))
    assert rel < 0.05, rel
    substrate.batched_gemm(q, kT, site="attn.pv", backend="arrayflex_w8a8")
    assert substrate.SITE_PLANS["attn.pv"].precision == "fp32"
    substrate.clear_plan_cache()


# --------------------------------------- model-level equivalence matrix
@pytest.mark.parametrize("arch", ["qwen2-0.5b", "qwen3-moe-30b-a3b",
                                  "mamba2-370m"])
def test_w8a8_forward_and_decode_match_fp32(arch):
    """w8a8 x {dense, MoE, Mamba}, unsharded: logits within the
    documented tolerance of the fp32 arrayflex backend, and the family's
    weight GEMMs really planned the w8a8 datapath."""
    toks = jnp.asarray(_TOKS, jnp.int32)
    params = _params(arch)
    want, _, _ = lm.forward(_cfg(arch, "arrayflex"), params,
                            {"tokens": toks})
    substrate.SITE_PLANS.clear()
    got, _, _ = lm.forward(_cfg(arch, "arrayflex_w8a8"), params,
                           {"tokens": toks})
    np.testing.assert_allclose(np.float32(got), np.float32(want),
                               atol=ATOL[arch])
    family = ({"mamba.z", "mamba.xbc", "mamba.out"} if arch == "mamba2-370m"
              else {"moe.wi_gate", "moe.wo"} if "moe" in arch
              else {"attn.wq", "mlp.wi_gate", "unembed"})
    for s in family:
        p = substrate.SITE_PLANS[s]
        assert p.backend == "arrayflex_w8a8" and p.precision == "w8a8", s
    tok = jnp.asarray([3, 5], jnp.int32)
    want, _ = lm.decode_step(_cfg(arch, "arrayflex"), params,
                             lm.init_cache(_cfg(arch), 2, 8), tok,
                             jnp.int32(0))
    got, _ = lm.decode_step(_cfg(arch, "arrayflex_w8a8"), params,
                            lm.init_cache(_cfg(arch), 2, 8), tok,
                            jnp.int32(0))
    np.testing.assert_allclose(np.float32(got), np.float32(want),
                               atol=ATOL[arch])


def test_w8a8_greedy_streams_bit_identical():
    """Acceptance: greedy streams are bit-identical run-to-run under
    w8a8.  Against fp32 the comparison is on logits, not tokens: with
    random weights a top-1 margin can be smaller than the quantization
    perturbation, so a token may flip while every logit along the w8a8
    stream stays within the documented W8A8 tolerance of fp32."""
    prompts = [[5, 6, 7], [11, 12, 13, 14], [21, 22]]

    def run(backend):
        cfg = _cfg("qwen2-0.5b", backend)
        eng = ServingEngine(cfg, _params("qwen2-0.5b"),
                            ServeConfig(max_batch=2, max_seq=32))
        reqs = [Request(prompt=p, max_new_tokens=4, rid=i)
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        eng.run_to_completion()
        return [r.out_tokens for r in reqs]

    first = run("arrayflex_w8a8")
    assert first == run("arrayflex_w8a8")        # run-to-run determinism
    assert all(len(t) == 4 for t in first)
    params = _params("qwen2-0.5b")
    for prompt, out in zip(prompts, first):
        # teacher-forced over the w8a8 stream: every logit that chose (or
        # would have chosen) a token sits within ATOL of fp32
        toks = jnp.asarray([prompt + out], jnp.int32)
        want, _, _ = lm.forward(_cfg("qwen2-0.5b", "arrayflex"), params,
                                {"tokens": toks})
        got, _, _ = lm.forward(_cfg("qwen2-0.5b", "arrayflex_w8a8"),
                               params, {"tokens": toks})
        np.testing.assert_allclose(np.float32(got), np.float32(want),
                                   atol=ATOL["qwen2-0.5b"])


def test_w8a8_one_launch_per_site():
    """The w8a8 backend keeps the fused/batched launch structure — one
    launch per site, including the fused swiglu pair and the
    expert-batched MoE sites."""
    for arch in ("qwen2-0.5b", "qwen3-moe-30b-a3b"):
        cfg = _cfg(arch, "arrayflex_w8a8")
        substrate.clear_plan_cache()
        jax.eval_shape(lambda p, b, c=cfg: lm.forward(c, p, b),
                       _params(arch), {"tokens": jnp.ones((2, 8), jnp.int32)})
        counts = dict(substrate.DISPATCH_COUNTS)
        assert all(v == 1 for v in counts.values()), counts
        if "moe" in arch:
            assert {"moe.router", "moe.wi_gate", "moe.wi_up",
                    "moe.wo"} <= set(counts)
        else:
            assert "mlp.wi_gate+mlp.wi_up" in counts
    substrate.clear_plan_cache()


# --------------------------------------- multi-device TP2 cells (8 dev)
@needs8
@pytest.mark.parametrize("arch", ["qwen2-0.5b", "qwen3-moe-30b-a3b",
                                  "mamba2-370m"])
def test_multidev_w8a8_tp2_matches_unsharded(arch):
    """w8a8 x {dense, MoE, Mamba} x TP2.  Unlike W8, TP2 w8a8 is NOT
    bit-exact vs unsharded w8a8 — row-parallel shards re-tile the
    contraction and the per-tile activation scales move with the tiling —
    but the drift is quantization-noise sized (same family tolerances),
    and TP2 stays within the documented bound of fp32 arrayflex."""
    toks = jnp.asarray(_TOKS, jnp.int32)
    params = _params(arch)
    un, _, _ = lm.forward(_cfg(arch, "arrayflex_w8a8"), params,
                          {"tokens": toks})
    tp, _, _ = lm.forward(_cfg(arch, "arrayflex_w8a8", (1, 2)), params,
                          {"tokens": toks})
    np.testing.assert_allclose(np.float32(tp), np.float32(un),
                               atol=ATOL[arch])
    fp, _, _ = lm.forward(_cfg(arch, "arrayflex"), params,
                          {"tokens": toks})
    np.testing.assert_allclose(np.float32(tp), np.float32(fp),
                               atol=ATOL[arch])


@needs8
def test_multidev_w8a8_tp2_stream_and_plans():
    """TP2 w8a8 greedy stream matches the unsharded w8a8 stream on the
    pinned prompts; row-parallel plans record w8a8 precision WITH the
    reduce boundary priced, and dispatch stays one launch per site."""
    params = _params("qwen2-0.5b")
    prompts = [[5, 6, 7], [11, 12, 13, 14], [21, 22]]

    def run(mesh):
        eng = ServingEngine(_cfg("qwen2-0.5b", "arrayflex_w8a8", mesh),
                            params, ServeConfig(max_batch=2, max_seq=32))
        reqs = [Request(prompt=p, max_new_tokens=4, rid=i)
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        eng.run_to_completion()
        return [r.out_tokens for r in reqs]

    assert run((1, 2)) == run(())
    substrate.clear_plan_cache()
    cfg = _cfg("qwen2-0.5b", "arrayflex_w8a8", (1, 2))
    jax.eval_shape(lambda p, b: lm.forward(cfg, p, b), params,
                   {"tokens": jnp.asarray(_TOKS, jnp.int32)})
    assert all(v == 1 for v in substrate.DISPATCH_COUNTS.values())
    wo = substrate.SITE_PLANS["attn.wo"]
    assert wo.precision == "w8a8" and wo.shard.reduce_ops == 1
    wq = substrate.SITE_PLANS["attn.wq"]
    assert wq.precision == "w8a8" and wq.shard.cols == 2
    substrate.clear_plan_cache()


# ------------------------------------------- tier-1 subprocess coverage
def test_w8a8_sharded_equivalence_subprocess():
    """On a single-device host, run the multidev w8a8 cells once in an
    8-device subprocess so tier-1 always covers the TP2 column."""
    if len(jax.devices()) >= 8:
        pytest.skip("multi-device host runs test_multidev_* directly")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q",
         os.path.join("tests", "test_w8a8_substrate.py"),
         "-k", "multidev"],
        capture_output=True, text=True, timeout=1500, env=env, cwd=REPO)
    assert out.returncode == 0, out.stdout[-4000:] + out.stderr[-2000:]
    assert "passed" in out.stdout
