"""Disaggregated prefill/decode serving (serving.disagg).

The acceptance matrix drives greedy streams through the colocated
:class:`ServingEngine` and the :class:`DisaggServingEngine` and requires
them bit-identical — dense and MoE families, xla / arrayflex /
arrayflex_w8a8 backends, 2+2 pods with and without the pp=2 layer
pipeline, dense and paged K/V, batched and token prefill.  On top of the
matrix: per-role plan pricing (prefill deepens ``best_k``, decode
shallows it — ``sharding.pp_transfer_terms``), the pod->pod K/V handoff
as a priced + chaos-faultable transfer, decode-pod-loss recovery through
the recompute-on-re-admission path, snapshot/restore with the prefill
cache, construction validations, and the AF002 stage-boundary audit leg
(``analysis.jaxpr_audit.audit_pipeline``).

The pp=2 cells and the pipeline audit need a 4-device host
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``).  On a
single-device host they skip in-process and run once through the
subprocess wrapper, so tier-1 always exercises them; the CI ``disagg``
job runs them directly.
"""
import dataclasses
import os
import subprocess
import sys

import jax
import pytest

from repro import configs
from repro.analysis import jaxpr_audit
from repro.configs import base
from repro.core import planner
from repro.kernels import substrate
from repro.models import lm
from repro.parallel import sharding
from repro.runtime.chaos import ChaosConfig
from repro.serving import (DisaggServeConfig, DisaggServingEngine,
                           EngineCrash, Request, ServeConfig, ServingEngine)
from repro.serving.disagg import PREFILL_STEP_OVERHEAD
from repro.serving.engine import PREFILL_CHUNK_CHOICES

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

needs4 = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs 4 host devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=4)")

BACKENDS = ("xla", "arrayflex", "arrayflex_w8a8")


def _cfg(arch="llama3-8b", backend="xla"):
    return base.reduced(configs.ARCHS[arch], gemm_backend=backend)


_PARAMS = {}


def _params(arch="llama3-8b", backend="xla"):
    # params are backend-independent (quantizing engines pre-quantize
    # internally), so cache per arch
    if arch not in _PARAMS:
        _PARAMS[arch] = lm.init_params(_cfg(arch), jax.random.PRNGKey(0))
    return _PARAMS[arch]


def _reqs():
    return [Request(prompt=[5, 7, 11, 13, 17, 19, 23], max_new_tokens=6,
                    rid=1),
            Request(prompt=[2, 3], max_new_tokens=5, rid=2),
            Request(prompt=[31], max_new_tokens=4, rid=3),
            Request(prompt=list(range(40, 60)), max_new_tokens=6, rid=4)]


def _run(engine_cls, sc, arch="llama3-8b", backend="xla", reqs=None):
    eng = engine_cls(_cfg(arch, backend), _params(arch, backend), sc)
    rs = _reqs() if reqs is None else reqs
    for r in rs:
        eng.submit(r)
    eng.run_to_completion()
    return {r.rid: (r.outcome, tuple(r.out_tokens)) for r in rs}, eng


_KW = dict(max_batch=4, max_seq=64, seed=0)


# ------------------------------------------------- equivalence matrix
@pytest.mark.parametrize("backend", BACKENDS)
def test_dense_disagg_stream_identical(backend):
    """2+2 pods, pp=1, dense K/V: bit-identical greedy streams per
    backend (W8A8 keeps the colocated chunk — tile geometry is part of
    its numerics — so within-backend equality is exact there too)."""
    colo, ce = _run(ServingEngine, ServeConfig(**_KW), backend=backend)
    dis, de = _run(DisaggServingEngine,
                   DisaggServeConfig(**_KW, prefill_pods=2, decode_pods=2),
                   backend=backend)
    assert dis == colo
    assert all(o == "ok" for o, _ in dis.values())
    if substrate.backend_act_quantizes(backend):
        assert de.prefill_chunk == ce.prefill_chunk
    assert de.stats["kv_transfer_bytes"] > 0
    assert set(de.ttft_virtual) == set(dis)


@pytest.mark.parametrize("backend", BACKENDS)
def test_moe_disagg_stream_identical(backend):
    """MoE family (token prefill — the batched path doesn't route
    experts), 2+2 pods, pp=1."""
    arch = "qwen3-moe-30b-a3b"
    colo, _ = _run(ServingEngine, ServeConfig(**_KW, prefill_mode="token"),
                   arch=arch, backend=backend)
    dis, _ = _run(DisaggServingEngine,
                  DisaggServeConfig(**_KW, prefill_mode="token",
                                    prefill_pods=2, decode_pods=2),
                  arch=arch, backend=backend)
    assert dis == colo


def test_paged_disagg_stream_identical():
    """Paged K/V: the handoff moves exactly the live pages the block
    table names, and streams stay bit-identical."""
    colo, _ = _run(ServingEngine, ServeConfig(**_KW))
    dis, eng = _run(DisaggServingEngine,
                    DisaggServeConfig(**_KW, kv_pages=40, page_size=16,
                                      prefill_pods=2, decode_pods=2))
    assert dis == colo
    assert eng.stats["kv_transfer_pages"] > 0
    assert eng.stats["kv_transfer_bytes"] > 0


@needs4
@pytest.mark.parametrize("backend", BACKENDS)
def test_multidev_pp2_stream_identical(backend):
    """pp=2 GPipe stages over each role's pod window (4 devices): the
    stage-boundary transfer re-prices plans per role but never moves
    values — streams stay bit-identical to the colocated engine."""
    colo, _ = _run(ServingEngine, ServeConfig(**_KW), backend=backend)
    dis, eng = _run(DisaggServingEngine,
                    DisaggServeConfig(**_KW, prefill_pods=2, decode_pods=2,
                                      pp_stages=2),
                    backend=backend)
    assert dis == colo
    assert eng.pp == 2


# ------------------------------------------------ launch accounting
def test_disagg_dispatch_accounting():
    substrate.DISPATCH_COUNTS.clear()
    dis, eng = _run(DisaggServingEngine,
                    DisaggServeConfig(**_KW, prefill_pods=2, decode_pods=2),
                    backend="arrayflex")
    assert all(o == "ok" for o, _ in dis.values())
    assert "attn.wq" in substrate.DISPATCH_COUNTS
    assert sum(substrate.DISPATCH_COUNTS.values()) > 0
    assert eng.stats["prefill_dispatches"] > 0
    assert eng.stats["decode_dispatches"] > 0
    # both role clocks advanced, and the virtual TTFT is bounded by the
    # colocated sum (it excludes the other role's interleaved work)
    assert eng.stats["prefill_time_s"] > 0
    assert eng.stats["decode_time_s"] > 0
    wall = {r: t for r, t in eng.ttft_virtual.items()}
    assert all(t > 0 for t in wall.values())


# ------------------------------------------------- per-role pricing
def test_role_pricing_k_shift():
    """The pinned boundary site (attn.wq of the reduced-8b geometry,
    M=K=896, one epilogue op, pp=2): prefill's boundary ops keep or
    deepen ``best_k``, decode's serialized ingress shallows it."""
    ep = substrate.Epilogue(kind="none", bias=True)
    assert ep.ops == 1

    def k(role, T):
        t_ops, t_cyc = sharding.pp_transfer_terms(role, 2, T, 896)
        sig = substrate.ShardSig(transfer_ops=t_ops,
                                 transfer_cycles=t_cyc)
        return substrate.plan_gemm(896, 896, T, backend="arrayflex",
                                   epilogue=ep, shard=sig).k

    assert (k("", 128), k("prefill", 128), k("decode", 128)) == (4, 4, 2)
    assert (k("", 2048), k("prefill", 2048), k("decode", 2048)) == (2, 2, 1)
    for T in (128, 2048):
        assert k("prefill", T) > k("decode", T)


def test_pp_transfer_terms():
    assert sharding.pp_transfer_terms("", 2, 8, 896) == (0, 0)
    assert sharding.pp_transfer_terms("prefill", 1, 8, 896) == (0, 0)
    assert sharding.pp_transfer_terms("prefill", 2, 8, 896) == (1, 0)
    assert sharding.pp_transfer_terms("prefill", 8, 8, 896) == (3, 0)
    ops_, cyc = sharding.pp_transfer_terms("decode", 2, 4, 896)
    assert ops_ == 0 and cyc == -(-(4 * 896) // substrate.ops.SA_C)
    with pytest.raises(ValueError, match="pp_role"):
        sharding.pp_transfer_terms("training", 2, 8, 896)


def test_pricing_scope_targets_boundary_site():
    """Inside use_pp_pricing only PP_BOUNDARY_SITE gets the pricing-only
    ShardCtx (mesh=None — the GPipe shard_map owns the 'pod' axis, the
    per-stage GEMM must not nest another)."""
    with sharding.use_pp_pricing("prefill", 2):
        ctx = sharding.gemm_shard_ctx(sharding.PP_BOUNDARY_SITE,
                                      8, 896, 896)
        assert ctx is not None and ctx.mesh is None
        assert ctx.transfer_ops == 1 and ctx.transfer_cycles == 0
        assert sharding.gemm_shard_ctx("mlp.wo", 8, 896, 896) is None
    with sharding.use_pp_pricing("decode", 2):
        ctx = sharding.gemm_shard_ctx(sharding.PP_BOUNDARY_SITE,
                                      4, 896, 896)
        assert ctx.transfer_cycles > 0 and ctx.transfer_ops == 0
    with sharding.use_pp_pricing("", 2):        # inert without a role
        assert sharding.gemm_shard_ctx(sharding.PP_BOUNDARY_SITE,
                                       8, 896, 896) is None


def test_prefill_chunk_repick():
    """The prefill role re-picks its chunk under PREFILL_STEP_OVERHEAD;
    an explicit serve_cfg.prefill_chunk still wins."""
    S = _KW["max_seq"]
    want = min(S, max(1, planner.attention_plan(
        S, S, choices=PREFILL_CHUNK_CHOICES,
        step_overhead=PREFILL_STEP_OVERHEAD)))
    eng = DisaggServingEngine(
        _cfg(), _params(),
        DisaggServeConfig(**_KW, prefill_pods=2, decode_pods=2))
    assert eng.prefill_chunk == want
    pinned = DisaggServingEngine(
        _cfg(), _params(),
        DisaggServeConfig(**_KW, prefill_chunk=8,
                          prefill_pods=2, decode_pods=2))
    assert pinned.prefill_chunk == 8


# --------------------------------------------------------- validations
def test_construction_validations():
    cfg, p = _cfg(), _params()
    with pytest.raises(TypeError, match="DisaggServeConfig"):
        DisaggServingEngine(cfg, p, ServeConfig(**_KW))
    with pytest.raises(ValueError, match="at least one pod"):
        DisaggServingEngine(cfg, p, DisaggServeConfig(**_KW,
                                                      prefill_pods=0))
    with pytest.raises(ValueError, match="prefix_cache"):
        DisaggServingEngine(cfg, p, DisaggServeConfig(
            **_KW, prefill_pods=2, decode_pods=2,
            kv_pages=40, page_size=16, prefix_cache=True))
    with pytest.raises(ValueError, match="dense K/V"):
        DisaggServingEngine(cfg, p, DisaggServeConfig(
            **_KW, prefill_pods=2, decode_pods=2, pp_stages=2,
            kv_pages=40, page_size=16))
    with pytest.raises(ValueError, match="prefill_pods == decode_pods"):
        DisaggServingEngine(cfg, p, DisaggServeConfig(
            **_KW, prefill_pods=1, decode_pods=2, pp_stages=2))


# --------------------------------------------------------------- chaos
def _streams(res):
    return {rid: toks for rid, (_, toks) in res.items()}


def test_chaos_transfer_retry_recovers():
    base_res, _ = _run(DisaggServingEngine,
                       DisaggServeConfig(**_KW, prefill_pods=2,
                                         decode_pods=2))
    res, eng = _run(DisaggServingEngine,
                    DisaggServeConfig(**_KW, prefill_pods=2, decode_pods=2,
                                      max_retries=2,
                                      chaos=ChaosConfig(kv_transfer_at=0)))
    assert res == base_res
    assert eng.stats["transfer_retries"] == 1
    assert all(o == "ok" for o, _ in res.values())


def test_chaos_transfer_persistent_fails_typed():
    rs = _reqs()
    eng = DisaggServingEngine(
        _cfg(), _params(),
        DisaggServeConfig(**_KW, prefill_pods=2, decode_pods=2,
                          max_retries=0,
                          chaos=ChaosConfig(kv_transfer=1.0)))
    for r in rs:
        eng.submit(r)
    eng.run_to_completion()
    bad = [r for r in rs if r.outcome == "failed"]
    assert bad
    assert all("TransferFault" in (r.error or "") for r in bad)


@pytest.mark.parametrize("paged", (False, True))
def test_chaos_decode_pod_loss_recovers(paged):
    """A decode pod dies mid-stream: every decode-resident request
    re-admits through the recompute path (prefilled again, handed off
    again) and finishes PREEMPTED_RETRIED with bit-identical tokens."""
    kv = dict(kv_pages=40, page_size=16) if paged else {}
    base_res, _ = _run(DisaggServingEngine,
                       DisaggServeConfig(**_KW, prefill_pods=2,
                                         decode_pods=2, **kv))
    res, eng = _run(DisaggServingEngine,
                    DisaggServeConfig(**_KW, prefill_pods=2, decode_pods=2,
                                      chaos=ChaosConfig(pod_lost_at=4),
                                      **kv))
    assert eng.stats["pod_losses"] == 1
    assert _streams(res) == _streams(base_res)
    assert any(o == "preempted_retried" for o, _ in res.values())


def test_snapshot_restore_with_pcache():
    """An injected crash mid-serve restores from the snapshot (which
    carries the prefill-role cache) and finishes bit-identically."""
    base_res, _ = _run(DisaggServingEngine,
                       DisaggServeConfig(**_KW, prefill_pods=2,
                                         decode_pods=2))
    sc = DisaggServeConfig(**_KW, prefill_pods=2, decode_pods=2,
                           snapshot_every_ticks=1,
                           chaos=ChaosConfig(crash_at=5))
    eng = DisaggServingEngine(_cfg(), _params(), sc)
    for r in _reqs():
        eng.submit(r)
    with pytest.raises(EngineCrash):
        eng.run_to_completion()
    snap = eng.latest_snapshot()
    assert snap is not None and "pcache" in snap
    eng2 = DisaggServingEngine.restore(_cfg(), _params(), sc, snap)
    eng2.run_to_completion()
    got = {r.rid: tuple(r.out_tokens) for r in eng2.restored_requests}
    want = _streams(base_res)
    for rid, toks in got.items():
        assert toks == want[rid], (rid, toks, want[rid])


# ------------------------------------------------- AF002 pipeline audit
@needs4
def test_multidev_audit_pipeline_roles_clean():
    cfg = _cfg()
    for role, off in (("prefill", 0), ("decode", 2)):
        rcfg = dataclasses.replace(cfg, pp_role=role, pp_stages=2,
                                   mesh_shape=(2, 1, 1), pod_offset=off)
        assert jaxpr_audit.audit_pipeline(rcfg) == []


@needs4
def test_multidev_audit_unscoped_pipeline_flags_af002():
    """The seeded violation: a pipelined step traced WITHOUT a role
    pricing scope stages its collective_permute with no site plan
    pricing the transfer."""
    bad = dataclasses.replace(_cfg(), pp_role="", pp_stages=2,
                              mesh_shape=(2, 1, 1))
    findings = jaxpr_audit.audit_pipeline(bad)
    af002 = [f for f in findings if f.code == "AF002"
             and "collective_permute" in f.message]
    assert af002, findings
    assert "use_pp_pricing" in af002[0].message


# ---------------------------------------------------- serve CLI + tier-1
def test_disagg_subprocess():
    """On a small host, run the 4-device cells once in a subprocess so
    tier-1 always covers the pp=2 matrix and the pipeline audit."""
    if len(jax.devices()) >= 4:
        pytest.skip("multi-device host runs test_multidev_* directly")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q",
         os.path.join("tests", "test_disagg.py"),
         "-k", "multidev"],
        capture_output=True, text=True, timeout=1500, env=env, cwd=REPO)
    assert out.returncode == 0, out.stdout[-4000:] + out.stderr[-2000:]
    assert "passed" in out.stdout


def test_serve_cli_disagg():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--reduced",
         "--requests", "3", "--max-new", "4", "--prefill-pods", "1",
         "--decode-pods", "1"],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
    assert "disagg: 1 prefill + 1 decode pod(s)" in out.stdout
    assert "virtual TTFT" in out.stdout
