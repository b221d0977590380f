"""Serving driver: chunked batched prefill + continuous-batching decode.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b \
      --requests 6 --max-new 24 --prefill-chunk 32

serves the published config (qwen2-0.5b: 24 layers, d_model 896) with
random weights from the seed; ``--reduced`` swaps in the tiny CPU-test
config.

Sharded SPMD serving: ``--tp``/``--fsdp`` declare the (data, model) host
mesh — every model GEMM then plans on its post-partition shape and runs
per-shard under jax.shard_map (see docs/substrate.md).  On CPU,
``--host-devices N`` fans the host out to N devices (the XLA_FLAGS
device-count override) so a TP=4 mesh is testable on a laptop:

  PYTHONPATH=src python -m repro.launch.serve --reduced --tp 4 \
      --host-devices 8

Prints per-request outputs plus per-phase timing: prefill and decode
throughput (tokens/s), dispatch counts, and mean time-to-first-token.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time

import jax

from repro.configs import get_config, reduced
from repro.kernels import substrate
from repro.launch.compile_cache import setup_compile_cache
from repro.models import lm
from repro.runtime import chaos
from repro.serving import (AdmissionError, DisaggServeConfig,
                           DisaggServingEngine, EngineCrash, ServeConfig,
                           ServingEngine)
from repro.serving.engine import Request


def phase_report(engine: ServingEngine, reqs) -> str:
    st = engine.stats
    pf_tps = st["prefill_tokens"] / max(st["prefill_time_s"], 1e-9)
    de_tps = st["decode_tokens"] / max(st["decode_time_s"], 1e-9)
    ttfts = [r.ttft_s for r in reqs if r.ttft_s is not None]
    ttft_ms = 1e3 * sum(ttfts) / max(len(ttfts), 1)
    out = (f"prefill[{engine.prefill_mode}]: {st['prefill_tokens']} tok "
           f"in {st['prefill_time_s']:.3f}s ({pf_tps:.1f} tok/s, "
           f"{st['prefill_dispatches']} dispatches, "
           f"chunk={engine.prefill_chunk})\n"
           f"decode: {st['decode_tokens']} tok in "
           f"{st['decode_time_s']:.3f}s ({de_tps:.1f} tok/s, "
           f"{st['decode_dispatches']} dispatches)\n"
           f"mean TTFT: {ttft_ms:.1f} ms")
    if engine.paged:
        out += (f"\npaged: peak {st['pages_used_peak']} pages, "
                f"peak concurrency {st['concurrency_peak']}, "
                f"prefix hits {st['prefix_hit_tokens']} tok, "
                f"{st['prefill_gemm_dispatches']} prefill GEMM launches")
    be = engine.cfg.gemm_backend
    if substrate.backend_quantizes(be):
        out += (f"\nquantized: {be} serves int8 weights from the "
                f"pre-quantized tree"
                + (", per-tile int8 activations in-kernel (W8A8 MAC path)"
                   if substrate.backend_act_quantizes(be)
                   else " against fp32 activations"))
    counts = {r.outcome or "pending": 0 for r in reqs}
    for r in reqs:
        counts[r.outcome or "pending"] += 1
    out += ("\noutcomes: "
            + ", ".join(f"{k}={v}" for k, v in sorted(counts.items())))
    resil = (f"\nresilience: {st['sample_retries']} sample retries, "
             f"{st['kernel_fault_retries']} kernel-fault retries, "
             f"{st['preemptions']} preemptions, "
             f"{st['watchdog_fired']} watchdog fires")
    if st["snapshots_taken"]:
        resil += f", {st['snapshots_taken']} snapshots"
    out += resil
    if isinstance(engine, DisaggServingEngine):
        sc = engine.sc
        vt = [engine.ttft_virtual[r.rid] for r in reqs
              if r.rid in engine.ttft_virtual]
        vt_ms = 1e3 * sum(vt) / max(len(vt), 1)
        makespan = max(st["prefill_time_s"], st["decode_time_s"])
        out += (f"\ndisagg: {sc.prefill_pods} prefill + {sc.decode_pods} "
                f"decode pod(s), pp={engine.pp}; "
                f"mean virtual TTFT {vt_ms:.1f} ms "
                f"(per-role clocks; wall TTFT above pays the colocated "
                f"interleave)\n"
                f"disagg: role makespan {makespan:.3f}s "
                f"(colocated sum {st['prefill_time_s'] + st['decode_time_s']:.3f}s), "
                f"K/V handoff {st['kv_transfer_bytes'] / 1024:.0f} KiB"
                + (f" in {st['kv_transfer_pages']} pages"
                   if engine.paged else "")
                + (f", {st['transfer_retries']} transfer retries"
                   if st["transfer_retries"] else "")
                + (f", {st['pod_losses']} pod losses"
                   if st["pod_losses"] else ""))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true",
                    help="serve the tiny same-family config (d_model 64, "
                         "2 layers) instead of the published widths")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="prefill chunk size (0 -> planner-chosen)")
    ap.add_argument("--prefill-mode", default="auto",
                    choices=("auto", "batched", "token"))
    ap.add_argument("--kv-pages", type=int, default=0,
                    help="paged K/V: physical pages in the global pool "
                         "(incl. the scratch page); 0 keeps the dense "
                         "(max_batch, max_seq) slot cache.  Admission then "
                         "reserves pages, so concurrency is memory-bounded "
                         "rather than capped at --max-batch")
    ap.add_argument("--page-size", type=int, default=0,
                    help="tokens per K/V page (must divide max_seq); "
                         "0 -> planner.page_plan picks it with the Eq.(6) "
                         "cost model")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix shared-prefix reuse: requests sharing a "
                         "prompt prefix map their leading block-table "
                         "entries to the same physical pages (paged mode "
                         "only)")
    ap.add_argument("--gemm-backend", default="xla",
                    help="GEMM substrate backend (kernels.substrate): "
                         + " | ".join(substrate.backends()))
    ap.add_argument("--prefill-pods", type=int, default=0,
                    help="disaggregated serving: pods in the prefill role "
                         "submesh (device window [0, prefill_pods)); "
                         "setting either pod flag switches to "
                         "DisaggServingEngine (see docs/serving.md)")
    ap.add_argument("--decode-pods", type=int, default=0,
                    help="disaggregated serving: pods in the decode role "
                         "submesh (devices after the prefill window)")
    ap.add_argument("--pp", type=int, default=1,
                    help="pipeline stages over the 'pod' axis within each "
                         "role (GPipe collective_permute); requires "
                         "--prefill-pods == --decode-pods == PP and dense "
                         "K/V")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree (mesh 'model' axis); "
                         "GEMMs plan per-shard and run under shard_map")
    ap.add_argument("--fsdp", type=int, default=1,
                    help="FSDP/data-parallel degree (mesh 'data' axis)")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="fan the host out to N devices before the backend "
                         "initializes (XLA_FLAGS "
                         "--xla_force_host_platform_device_count; CPU only)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request total deadline in ms (0 = none); "
                         "expired requests terminate with outcome "
                         "deadline_expired")
    ap.add_argument("--ttft-deadline-ms", type=float, default=0.0,
                    help="per-request time-to-first-token deadline in ms "
                         "(0 = none)")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bounded admission queue (0 = unbounded); overflow "
                         "is rejected typed with outcome rejected_overload")
    ap.add_argument("--chaos", default="",
                    help="seeded fault injection spec, e.g. "
                         "'seed=3,gemm=0.05,nan_at=2,crash_at=10' "
                         "(keys: seed, gemm, nan, pages, crash, + _at "
                         "variants; see docs/resilience.md)")
    ap.add_argument("--preempt-policy", default="none",
                    choices=("none", "youngest"),
                    help="on page-pool exhaustion mid-decode: 'youngest' "
                         "preempts the youngest resident sequence (release "
                         "pages, re-queue, recompute via the prefix cache) "
                         "instead of failing; also switches paged admission "
                         "to lazy page reservation")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="engine snapshot cadence in ticks for crash "
                         "recovery (0 = off; forced to 1 when --chaos "
                         "configures a crash)")
    ap.add_argument("--max-restarts", type=int, default=3,
                    help="restore-from-snapshot attempts after injected "
                         "engine crashes before giving up")
    ap.add_argument("--strict-audit", action="store_true",
                    help="routing violations (unknown/missing site= labels) "
                         "raise [AF007] RuntimeErrors at dispatch time, and "
                         "run_to_completion cross-checks every recorded "
                         "site against planner.model_gemms (see "
                         "docs/analysis.md)")
    args = ap.parse_args(argv)

    if args.host_devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.host_devices}")

    if args.strict_audit:
        os.environ["REPRO_STRICT_AUDIT"] = "1"
    setup_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    # validate at config-resolve time: a typo'd backend should die here
    # with the registered list, not deep inside the first traced dispatch
    substrate.check_backend(args.gemm_backend)
    cfg = dataclasses.replace(cfg, gemm_backend=args.gemm_backend)
    if args.tp > 1 or args.fsdp > 1:
        cfg = dataclasses.replace(cfg, mesh_shape=(args.fsdp, args.tp))
        print(f"mesh: data={args.fsdp} x model={args.tp} over "
              f"{len(jax.devices())} host devices")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    if args.prefix_cache and not args.kv_pages:
        ap.error("--prefix-cache requires --kv-pages (paged mode)")
    chaos_cfg = chaos.parse_spec(args.chaos) if args.chaos else None
    snapshot_every = args.snapshot_every
    if (chaos_cfg is not None and not snapshot_every
            and (chaos_cfg.crash > 0.0 or chaos_cfg.crash_at >= 0)):
        snapshot_every = 1      # crash chaos without snapshots cannot recover
    disagg = args.prefill_pods > 0 or args.decode_pods > 0
    sc_kwargs = dict(max_batch=args.max_batch,
                     max_seq=128,
                     prefill_mode=args.prefill_mode,
                     prefill_chunk=args.prefill_chunk,
                     kv_pages=args.kv_pages,
                     page_size=args.page_size,
                     prefix_cache=args.prefix_cache,
                     max_queue=args.max_queue,
                     deadline_ms=args.deadline_ms,
                     ttft_deadline_ms=args.ttft_deadline_ms,
                     preempt_policy=args.preempt_policy,
                     snapshot_every_ticks=snapshot_every,
                     chaos=chaos_cfg)
    if disagg:
        sc = DisaggServeConfig(prefill_pods=max(1, args.prefill_pods),
                               decode_pods=max(1, args.decode_pods),
                               pp_stages=max(1, args.pp),
                               **sc_kwargs)
        engine = DisaggServingEngine(cfg, params, sc)
        print(f"disagg: {sc.prefill_pods} prefill + {sc.decode_pods} decode "
              f"pod(s), pp={sc.pp_stages}, prefill_chunk="
              f"{engine.prefill_chunk}")
    else:
        if args.pp > 1:
            ap.error("--pp requires disaggregated serving "
                     "(--prefill-pods/--decode-pods)")
        sc = ServeConfig(**sc_kwargs)
        engine = ServingEngine(cfg, params, sc)
    if chaos_cfg is not None:
        print(f"chaos: {args.chaos} (snapshot every "
              f"{snapshot_every or 'never'} ticks)")
    if args.kv_pages:
        print(f"paged KV: {args.kv_pages} pages x {engine.page_size} tok "
              f"({engine.kv_cache_bytes()/1024:.0f} KiB resident K/V), "
              f"prefix_cache={'on' if args.prefix_cache else 'off'}")
    prompts = [[2 + (i * 7 + j) % 97 for j in range(5 + i % 3)]
               for i in range(args.requests)]
    reqs = [Request(prompt=p, max_new_tokens=args.max_new,
                    temperature=args.temperature, rid=i)
            for i, p in enumerate(prompts)]
    for r in reqs:
        try:
            engine.submit(r)
        except AdmissionError as e:
            print(f"req {r.rid}: rejected ({e})")
    t0 = time.time()
    ticks, restarts = 0, 0
    while True:
        try:
            ticks += engine.run_to_completion()
            break
        except EngineCrash as e:
            restarts += 1
            snap = engine.latest_snapshot()
            if snap is None or restarts > args.max_restarts:
                raise
            print(f"engine crashed ({e}); restoring from snapshot "
                  f"[restart {restarts}/{args.max_restarts}]")
            engine = type(engine).restore(cfg, params, sc, snap)
    dt = time.time() - t0
    # a restored engine rebuilt its Request objects from the snapshot:
    # merge by rid so reporting reflects the final state of every stream
    final = {r.rid: r for r in reqs}
    for r in engine.restored_requests:
        final[r.rid] = r
    reqs = [final[r.rid] for r in reqs]
    total = sum(len(r.out_tokens) for r in reqs)
    for r in reqs:
        print(f"req {r.rid}: prompt={r.prompt} -> {r.out_tokens} "
              f"[{r.outcome or 'pending'}]")
    print(f"{total} tokens in {dt:.2f}s ({total/max(dt,1e-9):.1f} tok/s, "
          f"{ticks} ticks)")
    if restarts:
        print(f"recovered from {restarts} injected crash(es)")
    print(phase_report(engine, reqs))
    return reqs


if __name__ == "__main__":
    main()
