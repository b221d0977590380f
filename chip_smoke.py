"""Smoke check on the chip: serve full-width qwen2-0.5b through the normal
serving path and check what comes out.

  python3 chip_smoke.py              # one TPU chip
  python3 chip_smoke.py --chips 4    # the multi-chip paths, on 4 chips

One chip: ``repro.launch.serve`` serves qwen2-0.5b at its published
widths (random weights from seed 0) with 4 requests of 16 new tokens,
under the ``xla`` and ``arrayflex`` GEMM backends, each once with the
dense K/V cache and once with paged K/V and the prefix cache.  Checks:
every request ends ``ok`` with 16 tokens; dense and paged streams are
bit-identical per backend; the lowered ``arrayflex`` decode step holds
the compiled Pallas kernel (``tpu_custom_call``); and the first-step
logits of ``lm.forward`` under ``arrayflex`` match ``xla``.

``--chips 4`` runs only the multi-chip paths: serving at
``--fsdp 2 --tp 2`` against the same seeded model on one device
(streams and first-step logits), and disaggregated serving at
``--prefill-pods 2 --decode-pods 2 --pp 2`` against colocated serving
(bit-identical streams).  Parameters and caches must span four devices.

Everything runs in this one process, which holds the chip(s).  Timings
printed here are host wall-clock smoke timings, compilation included:
not device metrics.  The last line of stdout is one JSON object,
``{"ok": true, "device": {...}}``; any failed check exits non-zero
without it, and so does a host where JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "qwen2-0.5b"
REQUESTS, MAX_NEW = 4, 16
KV_PAGES = 16            # 4 sequences x (128 / 64) pages + scratch, with room
# First-step logits, arrayflex vs xla: both run bf16 operands with fp32
# accumulation, but the xla backend rounds each GEMM to bf16 before its
# bias/residual add and the kernel adds in fp32 first, and the kernel's K
# padding changes the summation order.  Over 24 layers these bf16
# roundings give a max |diff| of a few percent of the logit range;
# a wrong kernel (a dropped K tile, a swapped operand) gives O(1).
LOGIT_TOL = 0.05         # max |a - b| / max |b|
# fsdp2 x tp2 against one device: the same kernels on per-shard shapes,
# psum in fp32; contraction splits change the bf16 summation order only.
SHARDED_LOGIT_TOL = 0.05


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    print(f"check ok: {what}", flush=True)


class Timer:
    """Per-phase host wall clock (compilation included)."""

    def __init__(self):
        self.phases = []

    def run(self, name, fn, *a, **kw):
        t0 = time.perf_counter()
        out = fn(*a, **kw)
        self.phases.append((name, time.perf_counter() - t0))
        return out

    def report(self):
        for name, dt in self.phases:
            print(f"smoke timing (host wall clock incl. compile, not a "
                  f"device metric): {name} {dt:.3f} s")


def serve(*extra):
    from repro.launch import serve as serve_cli
    argv = ["--arch", ARCH, "--requests", str(REQUESTS),
            "--max-new", str(MAX_NEW), *extra]
    print(f"\n$ python -m repro.launch.serve {' '.join(argv)}", flush=True)
    return serve_cli.main(argv)


def streams(reqs, label):
    for r in reqs:
        check(r.outcome == "ok" and len(r.out_tokens) == MAX_NEW,
              f"{label}: req {r.rid} ended {r.outcome} with "
              f"{len(r.out_tokens)} tokens")
    return [list(r.out_tokens) for r in reqs]


def model(backend, **overrides):
    import jax

    from repro.configs import get_config
    from repro.models import lm
    cfg = dataclasses.replace(get_config(ARCH), gemm_backend=backend,
                              **overrides)
    return cfg, lm.init_params(cfg, jax.random.PRNGKey(0))


def first_step_logits(cfg, params):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import lm
    toks = jnp.asarray([[2 + (i * 7 + j) % 97 for j in range(8)]
                        for i in range(REQUESTS)], jnp.int32)
    f = jax.jit(lambda p, t: lm.forward(cfg, p, {"tokens": t})[0][:, -1])
    return np.asarray(f(params, toks), np.float32)


def rel_err(a, b):
    import numpy as np
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def decode_step_text(cfg, params):
    """StableHLO of the jitted decode step, lowered from the real arrays."""
    import jax
    import jax.numpy as jnp

    from repro.models import lm
    cache = lm.init_cache(cfg, REQUESTS, 128)
    step = jax.jit(lambda p, c, t, pos: lm.decode_step(cfg, p, c, t, pos))
    return step.lower(params, cache, jnp.zeros((REQUESTS,), jnp.int32),
                      jnp.zeros((REQUESTS,), jnp.int32)).as_text()


def one_chip(timer: Timer) -> None:
    import numpy as np
    out = {}
    for backend in ("xla", "arrayflex"):
        dense = timer.run(f"serve {backend} dense", serve,
                          "--gemm-backend", backend)
        paged = timer.run(f"serve {backend} paged+prefix", serve,
                          "--gemm-backend", backend,
                          "--kv-pages", str(KV_PAGES), "--prefix-cache")
        d = streams(dense, f"{backend} dense")
        p = streams(paged, f"{backend} paged")
        check(d == p, f"{backend}: dense and paged streams bit-identical")
        out[backend] = d
    same = sum(a == b for a, b in zip(out["xla"], out["arrayflex"]))
    print(f"info: {same}/{REQUESTS} greedy streams agree between xla and "
          f"arrayflex (bf16 rounding may flip near-tied tokens)")

    cfg_af, params = model("arrayflex")
    text = timer.run("lower arrayflex decode step", decode_step_text,
                     cfg_af, params)
    check("tpu_custom_call" in text,
          "arrayflex decode step lowers to the compiled Pallas kernel "
          "(tpu_custom_call)")
    cfg_x = dataclasses.replace(cfg_af, gemm_backend="xla")
    la = timer.run("forward arrayflex", first_step_logits, cfg_af, params)
    lx = timer.run("forward xla", first_step_logits, cfg_x, params)
    check(bool(np.all(np.isfinite(la))) and la.shape == lx.shape,
          f"arrayflex first-step logits finite, shape {la.shape}")
    err = rel_err(la, lx)
    print(f"info: first-step logits arrayflex vs xla: max|diff|/max|xla| "
          f"= {err:.6f}")
    check(err <= LOGIT_TOL, f"arrayflex logits within {LOGIT_TOL} of xla "
                            f"(got {err:.6f})")


def leaf_devices(tree):
    import jax
    devs = set()
    for leaf in jax.tree.leaves(tree):
        devs |= set(leaf.sharding.device_set)
    return devs


def stream_logit_gaps(cfg, params, reqs):
    """Largest gap, over every token a server chose, between the top logit
    of ``cfg``'s model (teacher-forced on prompt + stream) and the logit
    of the chosen token, relative to the logit range: 0 when the server
    always picked this model's argmax."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import lm
    f = jax.jit(lambda p, t: lm.forward(cfg, p, {"tokens": t})[0][0])
    worst = 0.0
    for r in reqs:
        toks = list(r.prompt) + list(r.out_tokens)
        logits = np.asarray(f(params, jnp.asarray([toks[:-1]], jnp.int32)),
                            np.float32)
        n = len(r.prompt)
        for t, tok in enumerate(r.out_tokens):
            row = logits[n - 1 + t]
            worst = max(worst, float((row.max() - row[tok])
                                     / max(np.abs(row).max(), 1e-30)))
    return worst


def four_chips(timer: Timer) -> None:
    import jax

    from repro.serving import (DisaggServeConfig, DisaggServingEngine,
                               ServeConfig, ServingEngine)
    check(len(jax.devices()) >= 4, f"{len(jax.devices())} devices (need 4)")
    single_reqs = timer.run("serve single device", serve)
    single = streams(single_reqs, "single")
    sharded_reqs = timer.run("serve fsdp2 x tp2", serve,
                             "--fsdp", "2", "--tp", "2")
    sharded = streams(sharded_reqs, "fsdp2xtp2")
    same = sum(a == b for a, b in zip(single, sharded))
    print(f"info: {same}/{REQUESTS} fsdp2 x tp2 streams equal the "
          f"one-device streams (bf16 psum order may flip near-tied tokens)")
    cfg1, params = model("xla")
    cfg4 = dataclasses.replace(cfg1, mesh_shape=(2, 2))
    eng = ServingEngine(cfg4, params, ServeConfig(max_batch=REQUESTS,
                                                  max_seq=128))
    check(len(leaf_devices(eng.params)) == 4
          and len(leaf_devices(eng.cache)) == 4,
          "fsdp2 x tp2 engine parameters and cache span 4 devices")
    l1 = timer.run("forward one device", first_step_logits, cfg1, params)
    l4 = timer.run("forward fsdp2 x tp2", first_step_logits, cfg4,
                   eng.params)
    err = rel_err(l4, l1)
    print(f"info: first-step logits fsdp2xtp2 vs one device: "
          f"max|diff|/max|ref| = {err:.6f}")
    check(err <= SHARDED_LOGIT_TOL,
          f"fsdp2 x tp2 logits within {SHARDED_LOGIT_TOL} (got {err:.6f})")
    gap = timer.run("teacher-forced check", stream_logit_gaps, cfg1, params,
                    sharded_reqs)
    check(gap <= SHARDED_LOGIT_TOL,
          f"every fsdp2 x tp2 token is within {SHARDED_LOGIT_TOL} of the "
          f"one-device model's top logit (got {gap:.6f})")

    disagg = streams(timer.run("serve disagg 2+2 pp2", serve,
                               "--prefill-pods", "2", "--decode-pods", "2",
                               "--pp", "2"), "disagg")
    check(disagg == single, "disagg pp=2 streams bit-identical to colocated")
    deng = DisaggServingEngine(cfg1, params, DisaggServeConfig(
        max_batch=REQUESTS, max_seq=128, prefill_pods=2, decode_pods=2,
        pp_stages=2))
    check(len(leaf_devices(deng.pcache) | leaf_devices(deng.cache)) == 4,
          "disagg prefill and decode caches span 4 devices")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"platform={dev['platform']} device_kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        print("chip_smoke: JAX found no TPU; this check runs on the chip "
              "only", file=sys.stderr)
        return 1
    try:
        from repro.launch.compile_cache import setup_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script "
              f"({e})", file=sys.stderr)
        return 2
    print(f"compile cache: {setup_compile_cache()}", flush=True)

    timer = Timer()
    try:
        (four_chips if args.chips == 4 else one_chip)(timer)
    except SmokeFailure as e:
        timer.report()
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    timer.report()
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
