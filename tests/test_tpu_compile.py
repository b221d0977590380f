"""Compile the ArrayFlex kernels and the serving steps for a TPU v5e chip.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so these tests need no accelerator.  They catch
what interpret mode accepts and Mosaic refuses (blocks and in-kernel
slices off the 128-lane tile, scoped-VMEM overruns) at the published
widths of the benchmark models:

* every qwen2-0.5b GEMM site (d_model 896, d_ff 4864, vocab 151936) at a
  decode (T=4) and a prefill (T=512) row count, with the epilogues the
  model fuses, for fp32, int8 and w8a8, at k = 1, 2 and 4;
* the qwen3-moe-30b-a3b expert GEMMs (8 experts held, 2048 -> 768 and
  768 -> 2048) for fp32, int8 and w8a8;
* one full-width qwen2-0.5b decode step and one prefill step under the
  ``arrayflex`` backend, and the engine's paged decode step, whose kernel
  instructions must keep the names the chip benchmark reads and carry
  their substrate site labels as named scopes, and which, at the 0.5b
  offline cell's shape, writes its new K/V rows with no element-wise
  gather of the written pages;
* the qwen3-moe-30b-a3b paged decode and prefill steps at the widths and
  expert share of its benchmark cell (16 of 128 experts held, B 64, chunk
  64), two layers deep, with one kernel per site per layer under the
  names and scopes the chip benchmark reads.

Each compiled program must hold the Pallas kernel (``tpu_custom_call``)
and fit the chip's 16 GB of HBM.  Kernels pass ``interpret=False``
explicitly: on this CPU host the default would pick the interpreter.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.models import lm

HBM_BYTES = 16 * 10**9          # one TPU v5e chip
KS = (1, 2, 4)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip can be written to the persistent
    # cache but not read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile_and_check(fn, args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert used < HBM_BYTES, used
    return compiled


def _shapes(one_chip, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)


# (site, K, N, fused operands) of one qwen2-0.5b layer, plus the unembed
QWEN2_SITES = (
    ("attn.wq", 896, 896, ("bias", "norm_scale")),
    ("attn.wk", 896, 128, ("bias", "norm_scale")),
    ("attn.wo", 896, 896, ("residual",)),
    ("mlp.wi", 896, 4864, ("w2", "norm_scale")),      # dual swiglu
    ("mlp.wo", 4864, 896, ("residual",)),
    ("unembed", 896, 151936, ()),
)


@pytest.mark.parametrize("precision", ["fp32", "int8", "w8a8"])
def test_qwen2_gemm_sites_compile(one_chip, precision):
    quant = precision != "fp32"
    bf16, f32 = jnp.bfloat16, jnp.float32
    args, calls = [], []

    def sds(shape, dtype):
        args.append(jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip))
        return len(args) - 1

    for T in (4, 512):
        for site, K, N, fused in QWEN2_SITES:
            wdt = jnp.int8 if quant else bf16
            slots = dict(x=sds((T, K), bf16), w=sds((K, N), wdt))
            if quant:
                slots["w_scale"] = sds((N,), f32)
            if "w2" in fused:
                slots["w2"] = sds((K, N), wdt)
                if quant:
                    slots["w2_scale"] = sds((N,), f32)
            if "bias" in fused:
                slots["bias"] = sds((N,), f32)
            if "residual" in fused:
                slots["residual"] = sds((T, N), bf16)
            if "norm_scale" in fused:
                slots["norm_scale"] = sds((K,), f32)
            for k in KS:
                calls.append((site, k, slots))

    def fn(*a):
        outs = []
        for site, k, slots in calls:
            kw = {name: a[i] for name, i in slots.items()
                  if name not in ("x", "w")}
            outs.append(ops.arrayflex_matmul(
                a[slots["x"]], a[slots["w"]], k_collapse=k,
                act_quant=precision == "w8a8",
                activation="silu" if "w2" in kw else "none",
                out_dtype=f32 if site == "unembed" else None,
                interpret=False, **kw))
        return outs

    compiled = _compile_and_check(fn, args)
    assert compiled.as_text().count("tpu_custom_call") >= len(calls)


@pytest.mark.parametrize("precision", ["fp32", "int8", "w8a8"])
def test_qwen3_moe_expert_gemm_compiles(one_chip, precision):
    quant = precision != "fp32"
    E = 8                                # experts held on one chip
    shapes = []
    for T in (8, 256):
        for K, N in ((2048, 768), (768, 2048)):
            shapes.append((T, K, N))
    args = []
    for T, K, N in shapes:
        args.append(jax.ShapeDtypeStruct((E, T, K), jnp.bfloat16,
                                         sharding=one_chip))
        args.append(jax.ShapeDtypeStruct(
            (E, K, N), jnp.int8 if quant else jnp.bfloat16,
            sharding=one_chip))
        args.append(jax.ShapeDtypeStruct((E, N), jnp.float32,
                                         sharding=one_chip))

    def fn(*a):
        outs = []
        for i in range(len(shapes)):
            x, w, s = a[3 * i:3 * i + 3]
            for k in KS:
                outs.append(ops.arrayflex_expert_matmul(
                    x, w, w_scale=s if quant else None,
                    act_quant=precision == "w8a8", k_collapse=k,
                    interpret=False))
        return outs

    compiled = _compile_and_check(fn, args)
    assert compiled.as_text().count("tpu_custom_call") >= len(shapes) * 3


def _qwen2(one_chip):
    cfg = dataclasses.replace(get_config("qwen2-0.5b"),
                              gemm_backend="arrayflex",
                              pallas_interpret=False)
    params = _shapes(one_chip, jax.eval_shape(
        lambda: lm.init_params(cfg, jax.random.PRNGKey(0))))
    cache = _shapes(one_chip, jax.eval_shape(
        lambda: lm.init_cache(cfg, 4, 128)))
    return cfg, params, cache


def _i32(one_chip, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)


def test_qwen2_full_width_decode_step_compiles(one_chip):
    cfg, params, cache = _qwen2(one_chip)
    _compile_and_check(
        lambda p, c, t, pos: lm.decode_step(cfg, p, c, t, pos),
        (params, cache, _i32(one_chip, 4), _i32(one_chip, 4)))


def test_qwen2_full_width_prefill_step_compiles(one_chip):
    cfg, params, cache = _qwen2(one_chip)
    _compile_and_check(
        lambda p, c, t, pos, n: lm.prefill_step(cfg, p, c, t, pos, n),
        (params, cache, _i32(one_chip, 4, 128), _i32(one_chip, 4),
         _i32(one_chip, 4)))


def test_qwen2_paged_decode_kernels_keep_names_and_carry_sites(one_chip):
    """The engine's paged decode step at full width: every kernel
    instruction still reads as the chip benchmark's kernel events
    (``_gemm.N`` / ``_expert_gemm.N``), and the substrate's site labels
    reach the kernels' ``op_name`` metadata as named scopes."""
    import re
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]
                           / "benchmarks" / "chip"))
    from chipbench import harness, xtrace
    from repro.serving.engine import jit_step

    cfg, params, _ = _qwen2(one_chip)
    pages, page, B = 64, 16, 4
    cache = _shapes(one_chip, jax.eval_shape(
        lambda: lm.init_paged_cache(cfg, pages, page)))
    step = jit_step(lm.decode_step_paged, cfg)
    args = (params, cache, _i32(one_chip, B), _i32(one_chip, B),
            _i32(one_chip, B, 256 // page))
    assert step.lower(*args).as_text().startswith(
        "module @jit_decode_step_paged")
    text = step.lower(*args).compile().as_text()
    kernels = [ln.strip().removeprefix("ROOT ") for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    assert kernels
    event = re.compile(harness.KERNEL_EVENT)
    assert all(event.search(xtrace.op_name(k)) for k in kernels), \
        [xtrace.op_name(k) for k in kernels if not
         event.search(xtrace.op_name(k))]
    scopes = {m.group(1) for k in kernels
              for m in [re.search(r'op_name="[^"]*/([^/"]+)/jit\(_(?:expert_)?'
                                  r'gemm\)/', k)] if m}
    assert scopes == {"attn.wq", "attn.wk", "attn.wv", "attn.qk",
                      "attn.pv", "attn.wo", "mlp.wi_gate+mlp.wi_up",
                      "mlp.wo", "unembed"}, scopes


def _index_tuples(hlo_text):
    """Number of index tuples of every gather and scatter in ``hlo_text``:
    the index operand's elements over its index vector's length."""
    import math
    import re
    dims = {m.group(1): [int(d) for d in m.group(2).split(",") if d]
            for m in re.finditer(r"%([\w.\-]+) = \w+\[([\d,]*)\]",
                                 hlo_text)}
    counts = []
    for m in re.finditer(r"= \S+ (?:gather|scatter)\(([^)]*)\)(.*)",
                         hlo_text):
        idx = dims[m.group(1).split(", ")[1].split("%")[-1]]
        vdim = int(re.search(r"index_vector_dim=(\d+)", m.group(2)).group(1))
        counts.append(math.prod(idx) // (idx[vdim] if vdim < len(idx) else 1))
    return counts


def test_qwen2_paged_decode_writes_back_rows_not_gathered_pages(one_chip):
    """The paged decode step at the 0.5b offline cell's shape (B 128,
    page 16, max_seq 2048) writes each new K/V row into the pool: no
    gather or scatter takes one s32 index per element of the B written
    pages (B x page x KV x hd), the element-wise gather that took the
    written page back out of the gathered view."""
    from repro.serving.engine import jit_step

    cfg, params, _ = _qwen2(one_chip)
    B, page, max_seq = 128, 16, 2048
    cache = _shapes(one_chip, jax.eval_shape(
        lambda: lm.init_paged_cache(cfg, 1025, page)))
    args = (params, cache, _i32(one_chip, B), _i32(one_chip, B),
            _i32(one_chip, B, max_seq // page))
    text = jit_step(lm.decode_step_paged, cfg).lower(*args).compile() \
        .as_text()
    counts = _index_tuples(text)
    # the embedding row gather, the page lookups, the two K/V views
    # and the two row writes, once per layer in the scan body
    assert len(counts) >= 6, counts
    per_page = B * page * cfg.n_kv_heads * cfg.resolved_head_dim
    assert max(counts) < per_page, (counts, per_page)


def _instructions(hlo_text):
    """(opcode, dims or None for a tuple, root opcode of the computation a
    fusion calls or None) of every instruction in ``hlo_text``."""
    import re
    roots, found = {}, []
    comp = None
    for ln in hlo_text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) .*\{$", ln)
        if head:
            comp = head.group(1)
            continue
        m = re.match(r"\s*(ROOT )?%[\w.\-]+ = (\S+) ([\w\-]+)\(", ln)
        if not m:
            continue
        if m.group(1):
            roots[comp] = m.group(3)
        arr = re.match(r"\w+\[([\d,]*)\]", m.group(2))
        dims = ([int(d) for d in arr.group(1).split(",") if d]
                if arr else None)
        calls = re.search(r"calls=%([\w.\-]+)", ln)
        found.append((m.group(3), dims, calls and calls.group(1)))
    return [(op, dims, roots.get(c)) for op, dims, c in found]


@pytest.mark.parametrize("phase", ["decode", "prefill"])
@pytest.mark.parametrize("B, pages, max_seq", [(64, 20481, 256),
                                               (128, 16385, 2048)],
                         ids=["short-burst", "offline"])
def test_qwen2_paged_steps_update_the_pool_in_place(one_chip, phase, B,
                                                    pages, max_seq):
    """The paged steps as the engine launches them (pool donated) at the
    two qwen2-0.5b cells' shapes (chunk = B) make no pool-sized value:
    no instruction's result has the pool's page count in its shape apart
    from the parameters (the layer loop's own included), the output
    (bitcasts of the scatters' results), and the only instructions made
    of whole pages of the pool, at least one layer's pool in size, are the
    one scatter per pool leaf (which may see the pool as rows) and the
    fusions that run them.  The donated pool is aliased to the output."""
    import math
    from repro.serving.engine import donate_cache, jit_step

    cfg, params, _ = _qwen2(one_chip)
    page = 16
    cache = _shapes(one_chip, jax.eval_shape(
        lambda: lm.init_paged_cache(cfg, pages, page)))
    bt = _i32(one_chip, B, max_seq // page)
    if phase == "decode":
        fn, args = lm.decode_step_paged, (_i32(one_chip, B),
                                          _i32(one_chip, B), bt)
    else:
        fn, args = lm.prefill_step_paged, (_i32(one_chip, B, B),
                                           _i32(one_chip, B),
                                           _i32(one_chip, B), bt)
    compiled = donate_cache(jit_step(fn, cfg)).lower(
        params, cache, *args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    leaves = jax.tree.leaves(cache)
    layer_elems = math.prod(leaves[0].shape[1:])
    views = {"parameter", "get-tuple-element", "bitcast"}
    found = [(op, dims, root) for op, dims, root in _instructions(text)
             if dims is not None]
    paged = [op for op, dims, _ in found if pages in dims]
    assert paged and set(paged) <= views, sorted(set(paged))
    big = [(op, root) for op, dims, root in found
           if math.prod(dims) >= layer_elems
           and math.prod(dims) % (pages * page) == 0 and op not in views]
    assert sorted(op for op, _ in big if op != "fusion") == \
        ["scatter"] * len(leaves), big
    assert all(root == "scatter" for op, root in big if op == "fusion"), big
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= sum(
        math.prod(a.shape) * a.dtype.itemsize for a in leaves)
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert used < HBM_BYTES, used


@pytest.mark.parametrize("phase", ["decode", "prefill"])
def test_qwen3_moe_paged_steps_compile_with_one_kernel_per_site(one_chip,
                                                                phase):
    """The engine's paged steps for the qwen3-moe cell's configuration
    (held share 16 of 128, q/k norm) at B 64, chunk 64, max_seq 512, two
    of its layers (the scan body is one layer): each site's kernel reads as
    a chip-benchmark kernel event and carries its site's scope, once."""
    import re
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]
                           / "benchmarks" / "chip"))
    from chipbench import harness, xtrace
    from repro.serving.engine import jit_step

    base = get_config("qwen3-moe-30b-a3b")
    cfg = dataclasses.replace(
        base, n_layers=2, vocab_size=18992, gemm_backend="arrayflex",
        pallas_interpret=False, moe=dataclasses.replace(
            base.moe, experts_held=16))
    params = _shapes(one_chip, jax.eval_shape(
        lambda: lm.init_params(cfg, jax.random.PRNGKey(0))))
    cache = _shapes(one_chip, jax.eval_shape(
        lambda: lm.init_paged_cache(cfg, 2049, 16)))
    B, C, bt = 64, 64, _i32(one_chip, 64, 512 // 16)
    if phase == "decode":
        fn, args = lm.decode_step_paged, (_i32(one_chip, B),
                                          _i32(one_chip, B), bt)
    else:
        fn, args = lm.prefill_step_paged, (_i32(one_chip, B, C),
                                           _i32(one_chip, B),
                                           _i32(one_chip, B), bt)
    compiled = _compile_and_check(jit_step(fn, cfg),
                                  (params, cache, *args))
    kernels = [ln.strip().removeprefix("ROOT ")
               for ln in compiled.as_text().splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    event = re.compile(harness.KERNEL_EVENT)
    assert all(event.search(xtrace.op_name(k)) for k in kernels)
    scopes = sorted(m.group(1) for k in kernels
                    for m in [re.search(r'op_name="[^"]*/([^/"]+)/jit\('
                                        r'_(?:expert_)?gemm\)/', k)] if m)
    assert scopes == sorted(
        ["attn.wq", "attn.wk", "attn.wv", "attn.qk", "attn.pv", "attn.wo",
         "moe.router", "moe.wi_gate", "moe.wi_up", "moe.wo", "unembed"])
