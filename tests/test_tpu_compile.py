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
  ``arrayflex`` backend.

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
