"""Static-analysis subsystem: seeded violations for every finding code,
clean runs over the backend x model matrix, and the strict-audit runtime
enforcement.

Each seeded test plants exactly one contract violation and asserts the
matching pass fails loudly with the *distinct* finding code — proving the
auditor detects what it claims to detect, not just that clean code
passes.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from repro.analysis import ast_lint, jaxpr_audit, kernel_check
from repro.analysis.findings import CODES, Finding, Report
from repro.configs import get_config, reduced
from repro.core import planner
from repro.kernels import substrate
from repro.kernels.arrayflex_gemm import store_phase

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def codes(findings):
    return sorted({f.code for f in findings})


# ---------------------------------------------------------------------------
# findings / report plumbing

def test_finding_severity_defaults_from_codes():
    assert Finding("AF001", "x", "m").severity == "error"
    assert Finding("AF008", "x", "m").severity == "warning"
    assert Finding("ZZ999", "x", "m").severity == "error"   # unknown: strict


def test_report_exit_code_and_json():
    r = Report()
    r.extend([Finding("AF008", "a", "warn-only")])
    assert r.ok and r.exit_code == 0 and len(r.warnings) == 1
    r.extend([Finding("AF001", "b", "boom")])
    assert not r.ok and r.exit_code == 1
    d = r.to_dict()
    assert d["n_errors"] == 1 and d["n_warnings"] == 1
    assert d["findings"][1]["code"] == "AF001"


def test_every_code_documented():
    for code, (sev, desc) in CODES.items():
        assert sev in ("error", "warning") and desc, code


# ---------------------------------------------------------------------------
# jaxpr auditor: clean matrix

CLEAN_CELLS = [
    ("qwen2-0.5b", "xla"),
    ("qwen2-0.5b", "arrayflex"),
    ("qwen3-moe-30b-a3b", "arrayflex"),
    ("mamba2-370m", "arrayflex"),
]


@pytest.mark.parametrize("arch,backend", CLEAN_CELLS,
                         ids=[f"{a}-{b}" for a, b in CLEAN_CELLS])
def test_jaxpr_audit_clean(arch, backend):
    cfg = dataclasses.replace(reduced(get_config(arch)),
                              gemm_backend=backend)
    findings = jaxpr_audit.audit_model(cfg)
    errors = [f for f in findings if f.severity == "error"]
    assert not errors, "\n".join(str(f) for f in errors)


def test_jaxpr_audit_int8_warns_af008_only():
    """The int8 path necessarily stages quantize_weight under make_jaxpr
    (the ROADMAP W8A8 hoist): warnings, never errors."""
    cfg = dataclasses.replace(reduced(get_config("qwen2-0.5b")),
                              gemm_backend="arrayflex_int8")
    findings = jaxpr_audit.audit_model(cfg)
    assert not [f for f in findings if f.severity == "error"], \
        "\n".join(str(f) for f in findings)
    assert codes([f for f in findings if f.severity == "warning"]) \
        == ["AF008"]


def test_jaxpr_audit_int8_prequantized_clean():
    """With lm.prequantize_params hoisting quantization out of the trace
    (the serving-engine path), the int8 audit goes fully clean: the AF008
    staged-requantize warnings of the raw-tree path disappear."""
    cfg = dataclasses.replace(reduced(get_config("qwen2-0.5b")),
                              gemm_backend="arrayflex_int8")
    findings = jaxpr_audit.audit_model(cfg, prequantize=True)
    assert findings == [], "\n".join(str(f) for f in findings)


def test_jaxpr_audit_w8a8_prequantized_clean():
    """The W8A8 backend's dynamic activation quantization (in-kernel
    quantize_tile per tile, batched-QK _quantize of K) is declared via
    BackendInfo.act_quantize and priced by the Eq.(5') actq term — the
    auditor must classify it clean, not AF003/AF008."""
    cfg = dataclasses.replace(reduced(get_config("qwen2-0.5b")),
                              gemm_backend="arrayflex_w8a8")
    findings = jaxpr_audit.audit_model(cfg, prequantize=True)
    assert findings == [], "\n".join(str(f) for f in findings)


def test_jaxpr_audit_w8a8_raw_tree_warns_af008_only():
    """Raw-tree W8A8 stages weight quantization like W8: AF008 warnings
    only — the activation-quantize casts must not add AF003 errors."""
    cfg = dataclasses.replace(reduced(get_config("qwen2-0.5b")),
                              gemm_backend="arrayflex_w8a8")
    findings = jaxpr_audit.audit_model(cfg)
    assert not [f for f in findings if f.severity == "error"], \
        "\n".join(str(f) for f in findings)
    assert codes(findings) == ["AF008"]


def test_jaxpr_audit_w8a8_actq_declaration_is_load_bearing():
    """The same W8A8 trace audited WITHOUT the act_quantize declaration
    must flag the in-kernel activation casts as rogue AF003 — proving the
    classifier keys on the backend's declared capability, not on blanket
    int8-cast tolerance."""
    cfg = dataclasses.replace(reduced(get_config("qwen2-0.5b")),
                              gemm_backend="arrayflex_w8a8")
    entries = jaxpr_audit._trace_entries(cfg, prequantize=True)
    substrate.clear_plan_cache()
    try:
        closed = entries[0][1]()                    # forward
        undeclared = jaxpr_audit.audit_closed_jaxpr(
            closed, quantized=True, act_quantized=False)
        assert "AF003" in codes(undeclared)
        declared = jaxpr_audit.audit_closed_jaxpr(
            closed, quantized=True, act_quantized=True)
        assert declared == [], "\n".join(str(f) for f in declared)
    finally:
        substrate.clear_plan_cache()


# ---------------------------------------------------------------------------
# jaxpr auditor: seeded violations (one per code)

def test_seeded_af001_bypass_gemm():
    def bypass(x, w):
        return x @ w                    # test-file frames: unattributed

    closed = jax.make_jaxpr(bypass)(jnp.ones((4, 8)), jnp.ones((8, 4)))
    assert codes(jaxpr_audit.audit_closed_jaxpr(closed)) == ["AF001"]


def test_seeded_af002_bf16_psum_on_quantized_path():
    mesh = Mesh(np.array(jax.devices()[:1]), ("model",))
    f = jax.shard_map(lambda x: jax.lax.psum(x, "model"), mesh=mesh,
                      in_specs=P(), out_specs=P(), check_vma=False)
    closed = jax.make_jaxpr(f)(jnp.ones((4, 4), jnp.bfloat16))
    found = jaxpr_audit.audit_closed_jaxpr(closed, quantized=True)
    assert codes(found) == ["AF002"]
    # same trace on a non-quantized path, no substrate frames: tolerated
    assert jaxpr_audit.audit_closed_jaxpr(closed, quantized=False) == []


def test_seeded_af002_unpriced_psum_boundary():
    """Sharding-contract leg: a substrate psum staged while NO recorded
    plan priced a reduce boundary (ShardSig.reduce_ops == 0) trips AF002.

    Seeded through the real dispatch pipeline: a ShardCtx with forced
    ``reduce_axes`` over a 1-device mesh makes ``_sharded_gemm`` take the
    psum path while ``signature()`` prices ceil(log2(1)) == 0 reduce ops
    — exactly the 'combine tree rode free' drift the check exists for
    (the production sharding rules only set reduce_axes when tp > 1)."""
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    ctx = substrate.ShardCtx(mesh, P(None, "model"), P("model", None),
                             P(None, None), reduce_axes=("model",))
    x = jnp.ones((4, 8), jnp.float32)
    w = jnp.ones((8, 4), jnp.float32)
    substrate.clear_plan_cache()
    try:
        closed = jax.make_jaxpr(
            lambda a, b: substrate.gemm(a, b, site="mlp.wo", shard=ctx))(x, w)
        plan = substrate.SITE_PLANS["mlp.wo"]
        assert plan.shard.reduce_ops == 0          # the seeded mispricing
        found = jaxpr_audit.check_psum_boundaries(closed, quantized=True)
        assert found and codes(found) == ["AF002"]
        assert "reduce_ops" in found[0].message
        # same trace with the reduce priced somewhere: clean
        priced = dataclasses.replace(
            plan, shard=dataclasses.replace(plan.shard, reduce_ops=1))
        assert jaxpr_audit.check_psum_boundaries(
            closed, quantized=True, site_plans={"mlp.wo": priced}) == []
        # the leg only binds quantized backends (fp32 paths keep the
        # dtype-only AF002 semantics)
        assert jaxpr_audit.check_psum_boundaries(closed,
                                                 quantized=False) == []
    finally:
        substrate.clear_plan_cache()


def test_seeded_af003_rogue_int8_cast():
    closed = jax.make_jaxpr(
        lambda w: w.astype(jnp.int8).astype(jnp.float32) @ w)(
            jnp.ones((8, 8)))
    found = jaxpr_audit.audit_closed_jaxpr(closed)
    assert "AF003" in codes(found)


def test_seeded_af004_bf16_pallas_accumulator():
    def kernel(x_ref, o_ref, acc_ref):
        acc_ref[...] = x_ref[...].astype(jnp.bfloat16)
        o_ref[...] = acc_ref[...].astype(jnp.float32)

    f = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
        scratch_shapes=[pltpu.VMEM((8, 128), jnp.bfloat16)])
    closed = jax.make_jaxpr(f)(jnp.ones((8, 128), jnp.float32))
    assert codes(jaxpr_audit.audit_closed_jaxpr(closed)) == ["AF004"]


def test_seeded_af007_unknown_site_label():
    substrate.clear_plan_cache()
    try:
        found = jaxpr_audit.check_recorded_sites(
            counts={"attn.wq": 1, "bogus.site": 2})
        assert codes(found) == ["AF007"]
        assert "bogus.site" in found[0].message or \
            "bogus.site" in found[0].where
    finally:
        substrate.clear_plan_cache()


def test_seeded_af007_config_foreign_site():
    """A planner-known label that is not in this config's own GEMM walk
    still trips the per-config cross-check (e.g. an MoE site recorded
    while tracing a dense model)."""
    dense = reduced(get_config("qwen2-0.5b"))
    found = jaxpr_audit.check_recorded_sites(dense,
                                             counts={"moe.router": 1})
    assert codes(found) == ["AF007"]
    moe = reduced(get_config("qwen3-moe-30b-a3b"))
    assert jaxpr_audit.check_recorded_sites(moe,
                                            counts={"moe.router": 1}) == []


# ---------------------------------------------------------------------------
# kernel <-> timing consistency

def test_kernel_check_clean():
    assert kernel_check.run() == []


def test_seeded_af005_store_drops_bias():
    def broken_store(y, y2=None, w_scale=None, w2_scale=None, bias=None,
                     bias2=None, residual=None, activation="none"):
        return store_phase(y, y2, w_scale, w2_scale, None, bias2,
                           activation, residual)  # silently ignores bias

    found = kernel_check.check_epilogue_pricing(store_fn=broken_store)
    assert found and codes(found) == ["AF005"]
    assert all("bias=True" in f.where for f in found)


def test_seeded_af005_extra_unpriced_op():
    def gilded_store(y, y2=None, w_scale=None, w2_scale=None, bias=None,
                     bias2=None, residual=None, activation="none"):
        out = store_phase(y, y2, w_scale, w2_scale, bias, bias2,
                          activation, residual)
        return out * jnp.tanh(out)            # fused but never priced

    found = kernel_check.check_epilogue_pricing(store_fn=gilded_store)
    assert found and codes(found) == ["AF005"]


def test_seeded_af006_undeclared_gemmcall_field():
    keying = dict(substrate.CALL_FIELD_KEYING)
    del keying["bias"]                        # field with no keying story
    found = kernel_check.check_plan_key(call_keying=keying)
    assert codes(found) == ["AF006"]
    assert any("GemmCall.bias" in f.where for f in found)


def test_seeded_af006_stale_declaration_and_bad_attr():
    keying = dict(substrate.CALL_FIELD_KEYING)
    keying["ghost"] = "operand: field that no longer exists"
    keying["bias"] = "epilogue:no_such_attr"
    found = kernel_check.check_plan_key(call_keying=keying)
    assert codes(found) == ["AF006"] and len(found) == 2


def test_seeded_af006_noncompare_key_field():
    @dataclasses.dataclass(frozen=True)
    class LeakySig:
        rows: int = 1
        note: str = dataclasses.field(default="", compare=False)

    found = kernel_check.check_plan_key(shard_cls=LeakySig)
    assert codes(found) == ["AF006"]
    assert any("LeakySig.note" in f.where for f in found)


def test_seeded_af006_key_signature_drift():
    found = kernel_check.check_plan_key(
        key_params=("M", "N", "T", "backend", "epilogue"))
    assert codes(found) == ["AF006"]


# ---------------------------------------------------------------------------
# AST lint

def test_lint_repo_clean():
    found = ast_lint.run()
    assert found == [], "\n".join(str(f) for f in found)


def test_lint_seeded_violations(tmp_path):
    zone = tmp_path / "nn"
    zone.mkdir()
    (zone / "bad.py").write_text(textwrap.dedent("""\
        import jax.numpy as jnp
        from repro.kernels import substrate

        def sneaky(x, w):
            y = x @ w
            z = jnp.einsum("ij,jk->ik", x, w)
            h = substrate.gemm(x, w)
            g = substrate.gemm(x, w, site="totally.bogus")
            substrate.DISPATCH_COUNTS.clear()
            substrate.SITE_PLANS["x"] = None
            return y + z + h + g
    """))
    found = ast_lint.lint_paths([tmp_path], root=tmp_path)
    by_code = {c: [f for f in found if f.code == c] for c in codes(found)}
    assert codes(found) == ["AFL01", "AFL02", "AFL03"]
    assert len(by_code["AFL01"]) == 2       # `@` and einsum
    assert len(by_code["AFL02"]) == 2       # missing site=, bogus label
    assert len(by_code["AFL03"]) == 2       # .clear() and subscript write
    assert all(":" in f.where for f in found)   # file:line locations


def test_lint_seeded_paged_state_mutation(tmp_path):
    """AFL03's second ownership group: page-table/pool state may only be
    rewired inside serving/engine.py + serving/paged.py."""
    zone = tmp_path / "serving"
    zone.mkdir()
    (zone / "rogue.py").write_text(textwrap.dedent("""\
        def hijack(pool, seq, node):
            pool.free_pages.append(3)
            pool.refcounts[4] += 1
            seq.block_table[0] = 7
            node.children.pop(("a",))
            return seq
    """))
    found = ast_lint.lint_paths([tmp_path], root=tmp_path)
    assert codes(found) == ["AFL03"] and len(found) == 4
    assert all("serving/engine.py + serving/paged.py" in f.message
               for f in found)
    # the same file under an owner path is clean
    (zone / "engine.py").write_text((zone / "rogue.py").read_text())
    owned = ast_lint.lint_paths([zone / "engine.py"], root=tmp_path)
    assert owned == []


def test_lint_seeded_chaos_and_snapshot_state_mutation(tmp_path):
    """AFL03's chaos + snapshot ownership groups: chaos draw-state may
    only move inside runtime/chaos.py, engine snapshot state only inside
    serving/engine.py."""
    zone = tmp_path / "serving"
    zone.mkdir()
    (zone / "rogue.py").write_text(textwrap.dedent("""\
        def hijack(chaos_engine, engine):
            chaos_engine.chaos_draws["engine.tick"] = 0
            chaos_engine.chaos_draws.update({"pool.alloc": 9})
            chaos_engine.chaos_log.append(("engine.tick", 0, "forged"))
            engine._snapshots.pop()
            engine._snapshots[0] = {}
            return engine
    """))
    found = ast_lint.lint_paths([tmp_path], root=tmp_path)
    assert codes(found) == ["AFL03"] and len(found) == 5
    chaos_msgs = [f for f in found if "runtime/chaos.py" in f.message]
    snap_msgs = [f for f in found if "serving/engine.py" in f.message
                 and "snapshot" in f.message]
    assert len(chaos_msgs) == 3 and len(snap_msgs) == 2
    # the same mutations under the respective owner paths are clean
    rt = tmp_path / "runtime"
    rt.mkdir()
    (rt / "chaos.py").write_text(textwrap.dedent("""\
        def advance(self):
            self.chaos_draws["engine.tick"] = 1
            self.chaos_log.append(("engine.tick", 1, ""))
    """))
    assert ast_lint.lint_paths([rt / "chaos.py"], root=tmp_path) == []
    (zone / "engine.py").write_text(textwrap.dedent("""\
        def snap(self):
            self._snapshots[:] = [{}]
    """))
    assert ast_lint.lint_paths([zone / "engine.py"], root=tmp_path) == []


def test_lint_allowlist_and_forwarded_site(tmp_path):
    """ALLOWLIST functions may use raw GEMMs; a non-literal site= (a
    forwarder like nn.layers.linear) is left to the runtime check."""
    zone = tmp_path / "nn"
    zone.mkdir()
    (zone / "moe.py").write_text(textwrap.dedent("""\
        from repro.kernels import substrate

        def moe_apply_reference(x, w):
            return x @ w

        def linear(x, w, site):
            return substrate.gemm(x, w, site=site)
    """))
    assert ast_lint.lint_paths([tmp_path], root=tmp_path) == []


def test_lint_zones_exclude_kernels(tmp_path):
    """Raw contractions inside kernels/ are the substrate itself."""
    zone = tmp_path / "kernels"
    zone.mkdir()
    (zone / "somekernel.py").write_text("def f(x, w):\n    return x @ w\n")
    assert ast_lint.lint_paths([tmp_path], root=tmp_path) == []


# ---------------------------------------------------------------------------
# strict-audit runtime enforcement

def test_strict_audit_scope_raises_af007():
    x = jnp.ones((4, 8), jnp.float32)
    w = jnp.ones((8, 4), jnp.float32)
    substrate.clear_plan_cache()
    with substrate.strict_audit_scope():
        substrate.gemm(x, w, site="mlp.wo")          # known label: fine
        with pytest.raises(RuntimeError, match="AF007"):
            substrate.gemm(x, w, site="bogus.site")
    substrate.clear_plan_cache()


def test_strict_audit_env_and_contextvar(monkeypatch):
    monkeypatch.delenv("REPRO_STRICT_AUDIT", raising=False)
    assert not substrate.strict_audit_enabled()
    monkeypatch.setenv("REPRO_STRICT_AUDIT", "1")
    assert substrate.strict_audit_enabled()
    monkeypatch.setenv("REPRO_STRICT_AUDIT", "0")
    assert not substrate.strict_audit_enabled()


def test_strict_audit_off_records_unknown_site():
    """Outside strict mode the legacy behavior stands: unknown labels are
    recorded (and surface later via check_dispatch_sites / the auditor)."""
    x = jnp.ones((4, 8), jnp.float32)
    w = jnp.ones((8, 4), jnp.float32)
    substrate.clear_plan_cache()
    try:
        substrate.gemm(x, w, site="bogus.site")
        assert substrate.DISPATCH_COUNTS.get("bogus.site") == 1
        with pytest.raises(RuntimeError, match="AF007"):
            substrate.check_dispatch_sites()
    finally:
        substrate.clear_plan_cache()


def test_check_dispatch_sites_clean():
    x = jnp.ones((4, 8), jnp.float32)
    w = jnp.ones((8, 4), jnp.float32)
    substrate.clear_plan_cache()
    try:
        substrate.gemm(x, w, site="mlp.wo")
        substrate.check_dispatch_sites()             # no raise
    finally:
        substrate.clear_plan_cache()


def test_site_registry_covers_model_gemms():
    reg = planner.site_registry()
    assert {"attn.wq", "mlp.wo", "moe.router", "mamba.out",
            "unembed"} <= reg
    assert "bogus.site" not in reg


# ---------------------------------------------------------------------------
# the CLI, end to end (subprocess: owns XLA_FLAGS for the TP2 column)

def test_audit_cli_clean_with_tp2(tmp_path):
    out = tmp_path / "audit.json"
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    res = subprocess.run(
        [sys.executable, "-m", "repro.analysis.audit",
         "--models", "qwen2-0.5b", "--backends", "xla", "arrayflex_int8",
         "--out", str(out)],
        capture_output=True, text=True, env=env, cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    import json
    data = json.loads(out.read_text())
    assert data["ok"] and data["n_errors"] == 0
    tags = [c["cell"] for c in data["meta"]["cells"]]
    assert "qwen2-0.5b/xla/tp2" in tags
    assert "qwen2-0.5b/arrayflex_int8/unsharded" in tags
    # int8 cells carry the staged-quantize warning, by design
    assert data["n_warnings"] > 0
    assert all(f["code"] == "AF008" for f in data["findings"])
