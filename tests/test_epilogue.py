"""Fused epilogues, expert-batched dispatch, dispatch counts, plan-cache
hygiene and the Pallas interpret override.

Equivalence contract: for every backend, ``substrate.gemm`` with an
epilogue computes the same function as the unfused xla composition
(``act(x@w [+b]) [* (x@w2 [+b2])]``) to fp32-accumulation tolerance,
across ragged / prime / empty shapes.  The expert-batched kernel matches
the einsum and ``moe_apply`` stays equal to ``moe_apply_reference``.
Dispatch counts prove the fusion/batching is structural: one launch per
MoE expert-GEMM site, one launch for the dense swiglu pair.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS, reduced
from repro.configs.base import ShapeConfig
from repro.core import planner, timing
from repro.kernels import ops, substrate
from repro.kernels.arrayflex_gemm import arrayflex_expert_gemm
from repro.kernels.runtime import resolve_interpret
from repro.models import lm
from repro.nn import moe as moe_lib


def _unfused(x, w, w2, b, b2, kind):
    y = x @ w
    if b is not None:
        y = y + b
    if kind == "silu":
        y = jax.nn.silu(y)
    elif kind == "gelu":
        y = jax.nn.gelu(y)
    elif kind == "swiglu":
        u = x @ w2
        if b2 is not None:
            u = u + b2
        y = jax.nn.silu(y) * u
    return y


# ----------------------------------------------------------- fused epilogues
@pytest.mark.parametrize("backend", ["xla", "arrayflex", "ref"])
@pytest.mark.parametrize("kind,use_bias,use_bias2", [
    ("none", True, False),          # plain fused bias
    ("silu", False, False),
    ("gelu", True, False),
    ("swiglu", False, False),
    ("swiglu", True, True),
])
@pytest.mark.parametrize("shape", [
    (7, 33, 40),        # small ragged everything
    (130, 257, 384),    # prime-ish K beyond the SA tile, ragged M
    (128, 128, 128),    # exact tiling
])
def test_epilogue_matches_unfused(backend, kind, use_bias, use_bias2,
                                  shape):
    T, K, N = shape
    rng = np.random.RandomState(sum(shape) + len(kind))
    x = jnp.asarray(rng.randn(2, T, K), jnp.float32)
    w = jnp.asarray(rng.randn(K, N), jnp.float32)
    w2 = jnp.asarray(rng.randn(K, N), jnp.float32) if kind == "swiglu" \
        else None
    b = jnp.asarray(rng.randn(N), jnp.float32) if use_bias else None
    b2 = jnp.asarray(rng.randn(N), jnp.float32) if use_bias2 else None
    got = substrate.gemm(x, w, backend=backend, epilogue=kind, w2=w2,
                         bias=b, bias2=b2)
    want = _unfused(x, w, w2, b, b2, kind)
    np.testing.assert_allclose(np.float32(got), np.float32(want),
                               rtol=1e-4, atol=1e-3)


def test_epilogue_empty_shapes():
    """K=0 applies the epilogue to the zero accumulator (NOT plain zeros);
    empty rows/cols return empty results of the right shape."""
    b = jnp.asarray([1.0, -2.0, 3.0], jnp.float32)
    for backend in ("xla", "arrayflex", "ref"):
        got = substrate.gemm(jnp.zeros((4, 0), jnp.float32),
                             jnp.zeros((0, 3), jnp.float32),
                             backend=backend, epilogue="silu", bias=b)
        want = jnp.broadcast_to(jax.nn.silu(b), (4, 3))
        np.testing.assert_allclose(np.float32(got), np.float32(want),
                                   rtol=1e-5, atol=1e-6)
        empty = substrate.gemm(jnp.zeros((0, 5), jnp.float32),
                               jnp.zeros((5, 3), jnp.float32),
                               backend=backend, epilogue="gelu", bias=b)
        assert empty.shape == (0, 3)


def test_epilogue_validation():
    x, w = jnp.ones((2, 4)), jnp.ones((4, 8))
    with pytest.raises(ValueError):
        substrate.gemm(x, w, epilogue="swiglu")          # missing w2
    with pytest.raises(ValueError):
        substrate.gemm(x, w, epilogue="silu", w2=w)      # w2 without swiglu
    with pytest.raises(ValueError):
        substrate.gemm(x, w, bias2=jnp.ones((8,)))       # bias2 without w2
    with pytest.raises(ValueError):
        substrate.gemm(x, w, epilogue="tanh")            # unknown kind


def test_epilogue_priced_into_plan():
    """Eq.(5')/(6'): the fused epilogue adds to the per-step period, the
    dual contraction doubles the streamed cycles, and the plan cache keys
    on the epilogue spec."""
    substrate.clear_plan_cache()
    plain = substrate.plan_gemm(512, 256, 64, "arrayflex")
    ep = substrate.Epilogue(kind="swiglu", bias=True)
    fused = substrate.plan_gemm(512, 256, 64, "arrayflex", ep)
    assert fused is not plain
    assert fused.t_pred_ps > 2 * plain.t_pred_ps          # 2 contractions + e
    # the conventional comparator carries the SAME epilogue datapath, so
    # saving isolates the pipelining technique
    assert fused.t_conventional_ps > 2 * plain.t_conventional_ps
    assert ep.ops == 3 and ep.contractions == 2           # silu+gate+bias
    # the epilogue term is k-independent while cycles fall with k, so the
    # argmin can only move toward deeper collapse
    assert fused.k >= plain.k
    # timing-level sanity: period grows by exactly ops * d_epilogue_ps
    tp = timing.DEFAULT_TIMING
    assert tp.clock_period_ps(2, 3) == pytest.approx(
        tp.clock_period_ps(2) + 3 * tp.d_epilogue_ps)
    assert tp.clock_ghz(2, 3) == pytest.approx(
        1000.0 / tp.clock_period_ps(2, 3))


def test_analytic_and_executed_swiglu_plans_agree():
    """planner.model_gemms marks the wi pair with epilogue_ops=3 (silu +
    gate + the fused ln2 norm-scale prologue), so the analytic table and
    the executed fused substrate plan pick the same k and the two
    per-entry times sum to the dual-contraction prediction."""
    g = planner.GEMM("mlp.wi_gate", 512, 256, 64, epilogue_ops=3)
    lp = planner.plan_gemm(g, 128, 128)
    sp = substrate.plan_gemm(512, 256, 64, "arrayflex",
                             substrate.Epilogue(kind="swiglu",
                                                norm_scale=True))
    assert sp.epilogue.ops == 3
    assert lp.k == sp.k
    assert 2 * lp.t_abs_ps == pytest.approx(sp.t_pred_ps)
    assert lp.clock_ghz == pytest.approx(
        timing.DEFAULT_TIMING.clock_ghz(lp.k, 3))
    wi = [x for x in planner.model_gemms(reduced(ARCHS["qwen2-0.5b"]),
                                         ShapeConfig("t", 8, 2, "train"))
          if x.name.startswith("mlp.wi")]
    assert wi and all(x.epilogue_ops == 3 for x in wi)


# ------------------------------------------------- expert-batched kernel
@pytest.mark.parametrize("E,T,K,N", [
    (3, 5, 16, 24),      # small ragged
    (4, 130, 257, 40),   # rows/contraction beyond the SA tile, prime K
    (2, 128, 64, 128),   # exact tiling
])
def test_expert_batched_kernel_matches_einsum(E, T, K, N):
    rng = np.random.RandomState(E + T + K + N)
    x = jnp.asarray(rng.randn(E, T, K), jnp.float32)
    w = jnp.asarray(rng.randn(E, K, N), jnp.float32)
    want = jnp.einsum("etk,ekn->etn", x, w)
    for k in (1, 2, 4):
        got = ops.arrayflex_expert_matmul(x, w, k_collapse=k)
        np.testing.assert_allclose(np.float32(got), np.float32(want),
                                   rtol=1e-4, atol=1e-3)


def test_expert_batched_kernel_empty():
    out = arrayflex_expert_gemm(jnp.zeros((0, 4, 8), jnp.float32),
                                jnp.zeros((0, 8, 16), jnp.float32))
    assert out.shape == (0, 4, 16)
    out = ops.arrayflex_expert_matmul(jnp.zeros((2, 4, 0), jnp.float32),
                                      jnp.zeros((2, 0, 16), jnp.float32))
    assert not np.any(np.asarray(out)) and out.shape == (2, 4, 16)


def test_moe_apply_matches_reference_under_arrayflex():
    """The batched expert kernel inside moe_apply agrees with the dense
    every-expert oracle when capacity is ample."""
    cfg = reduced(ARCHS["qwen3-moe-30b-a3b"], compute_dtype="float32",
                  param_dtype="float32")
    m = cfg.moe
    key = jax.random.PRNGKey(0)
    p = moe_lib.moe_init(key, cfg.d_model, m.expert_d_ff or cfg.d_ff,
                         m.num_experts, num_shared=m.num_shared_experts,
                         dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model),
                          jnp.float32)
    want = moe_lib.moe_apply_reference(p, x, top_k=m.top_k)
    for backend in ("xla", "arrayflex"):
        got, _ = moe_lib.moe_apply(p, x, top_k=m.top_k,
                                   capacity_factor=8.0,
                                   compute_dtype=jnp.float32,
                                   backend=backend)
        np.testing.assert_allclose(np.float32(got), np.float32(want),
                                   rtol=1e-4, atol=1e-3)


# --------------------------------------------------------- dispatch counts
def test_one_launch_per_moe_expert_site():
    """Acceptance: per MoE layer the expert GEMMs dispatch 3 launches
    (one per site), not 3E — and the dense swiglu pair is ONE launch."""
    cfg = reduced(ARCHS["qwen3-moe-30b-a3b"], compute_dtype="float32",
                  param_dtype="float32", gemm_backend="arrayflex")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    substrate.clear_plan_cache()
    jax.eval_shape(lambda p, b: lm.forward(cfg, p, b), params,
                   {"tokens": jnp.ones((2, 8), jnp.int32)})
    counts = dict(substrate.DISPATCH_COUNTS)
    # one traced super-block layer (scan): exactly one launch per site
    assert counts["moe.wi_gate"] == 1
    assert counts["moe.wi_up"] == 1
    assert counts["moe.wo"] == 1
    # E > 1 proves batching is doing work, not a degenerate expert count
    assert cfg.moe.num_experts > 1
    # attention QK/PV dispatch through the substrate now
    assert counts.get("attn.qk", 0) >= 1
    assert counts.get("attn.pv", 0) >= 1
    # dense model: the swiglu pair is ONE fused dual-GEMM launch, recorded
    # under both component site labels
    cfg_d = reduced(ARCHS["qwen2-0.5b"], compute_dtype="float32",
                    param_dtype="float32", gemm_backend="arrayflex")
    params_d = lm.init_params(cfg_d, jax.random.PRNGKey(0))
    substrate.clear_plan_cache()
    jax.eval_shape(lambda p, b: lm.forward(cfg_d, p, b), params_d,
                   {"tokens": jnp.ones((2, 8), jnp.int32)})
    counts_d = dict(substrate.DISPATCH_COUNTS)
    assert counts_d["mlp.wi_gate+mlp.wi_up"] == 1
    assert "mlp.wi_gate" not in counts_d      # no separate unfused launches
    assert {"mlp.wi_gate", "mlp.wi_up"} <= set(substrate.SITE_PLANS)
    plan = substrate.SITE_PLANS["mlp.wi_gate"]
    assert plan.epilogue.kind == "swiglu" and plan.epilogue.contractions == 2


def test_expert_site_plans_consistent_across_backends():
    """Satellite: every backend records ONE plan per expert shape with the
    xla convention T = G*C (the unrolled path used to log expert 0 only,
    with a per-expert T=C)."""
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 3, 5, 16), jnp.float32)   # (G,E,C,K)
    w = jnp.asarray(rng.randn(3, 16, 24), jnp.float32)
    for backend in ("xla", "arrayflex", "ref"):
        substrate.clear_plan_cache()
        substrate.expert_gemm(x, w, site="moe.wi_gate", backend=backend)
        plan = substrate.SITE_PLANS["moe.wi_gate"]
        assert (plan.M, plan.N, plan.T) == (24, 16, 2 * 5)
        assert plan.backend == backend
        assert substrate.DISPATCH_COUNTS["moe.wi_gate"] == 1


def test_backend_overrides_honored_on_batched_paths():
    """Re-registering a built-in backend must win on batched_gemm and
    expert_gemm exactly as it does on gemm, and the unrolled custom path
    must count one launch per batch element."""
    rng = np.random.RandomState(0)
    xb = jnp.asarray(rng.randn(3, 4, 8), jnp.float32)
    wb = jnp.asarray(rng.randn(3, 8, 6), jnp.float32)
    xe = jnp.asarray(rng.randn(2, 3, 4, 8), jnp.float32)
    we = jnp.asarray(rng.randn(3, 8, 6), jnp.float32)
    calls = []

    def spy(x2, w, plan, call):
        calls.append(x2.shape)
        return x2 @ w

    orig = substrate._BACKENDS["xla"]
    substrate.register_backend("xla", spy)
    try:
        substrate.clear_plan_cache()
        got = substrate.batched_gemm(xb, wb, site="attn.qk", backend="xla")
        np.testing.assert_allclose(np.float32(got),
                                   np.float32(jnp.matmul(xb, wb)),
                                   rtol=1e-5, atol=1e-5)
        assert len(calls) == 3                      # unrolled per batch elem
        assert substrate.DISPATCH_COUNTS["attn.qk"] == 3   # honest count
        calls.clear()
        got = substrate.expert_gemm(xe, we, site="moe.wo", backend="xla")
        want = jnp.einsum("gecd,edf->gecf", xe, we)
        np.testing.assert_allclose(np.float32(got), np.float32(want),
                                   rtol=1e-5, atol=1e-5)
        assert len(calls) == 3                      # one per expert
        assert substrate.DISPATCH_COUNTS["moe.wo"] == 3
    finally:
        substrate.register_backend("xla", orig)
        substrate.clear_plan_cache()


# ------------------------------------------------------- plan-cache hygiene
def test_clear_plan_cache_clears_every_memo():
    """Satellite: clear_plan_cache must reset ops.plan_collapse and
    planner.attention_plan too, or stale picks leak across timing-param
    changes."""
    substrate.plan_gemm(512, 256, 64, "arrayflex")
    ops.plan_collapse(384, 192, 48)
    planner.attention_plan(4096, 32768)
    assert substrate.plan_cache_info().currsize > 0
    assert ops.plan_collapse.cache_info().currsize > 0
    assert planner.attention_plan.cache_info().currsize > 0
    substrate.SITE_PLANS["x"] = substrate.plan_gemm(8, 8, 8, "xla")
    substrate.DISPATCH_COUNTS["x"] = 3
    substrate.clear_plan_cache()
    assert substrate.plan_cache_info().currsize == 0
    assert ops.plan_collapse.cache_info().currsize == 0
    assert planner.attention_plan.cache_info().currsize == 0
    assert not substrate.SITE_PLANS and not substrate.DISPATCH_COUNTS


# --------------------------------------------------------- interpret plumbing
def test_resolve_interpret_chain(monkeypatch):
    assert resolve_interpret(None) is True         # CPU: interpreted
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
    # on a TPU backend the default compiles, and interpret mode is refused
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret(None) is False
    assert resolve_interpret(False) is False
    with pytest.raises(ValueError, match="interpret mode"):
        resolve_interpret(True)


def test_config_interpret_reaches_kernels(monkeypatch):
    """ModelConfig.pallas_interpret threads through substrate/ops down to
    pallas_call (observable: interpret=False on CPU fails to lower)."""
    import dataclasses
    cfg = reduced(ARCHS["qwen2-0.5b"], compute_dtype="float32",
                  param_dtype="float32", gemm_backend="arrayflex")
    assert cfg.pallas_interpret is None       # default: resolve chain
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    toks = jnp.ones((1, 4), jnp.int32)
    lm.forward(cfg, params, {"tokens": toks})  # interpret on CPU: runs
    cfg_hw = dataclasses.replace(cfg, pallas_interpret=False)
    with pytest.raises(Exception):
        # compiled Mosaic lowering is unavailable on CPU — proof the flag
        # reached the kernel (interpret=True would have succeeded)
        jax.block_until_ready(
            lm.forward(cfg_hw, params, {"tokens": toks})[0])
