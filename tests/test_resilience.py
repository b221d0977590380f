"""Resilient serving: seeded chaos, typed outcomes, graceful degradation.

The acceptance contract of the resilience layer, test by test:

* under every injected fault, every request terminates with exactly one
  typed :class:`~repro.serving.errors.Outcome` — no hang, no silent
  garbage in the stream;
* chaos is replayable: an injection decision is a pure function of
  (seed, point, draw index), independent of interleaving;
* a request preempted mid-decode (page-pool pressure) and recomputed on
  re-admission emits a stream bit-identical to an un-preempted run;
* an engine killed mid-stream and restored from its snapshot continues
  bit-identically;
* with chaos off, the hardened engine's streams are bit-identical to the
  unhardened baseline (resilience is free in the fault-free path).
"""
import dataclasses

import jax
import pytest

from repro.configs import ARCHS, reduced
from repro.models import lm
from repro.runtime import chaos
from repro.runtime.chaos import ChaosConfig, ChaosEngine
from repro.serving import (AdmissionError, EngineCrash, Outcome,
                           ServeConfig, ServingEngine)
from repro.serving.engine import Request
from repro.serving.paged import PagePool, RadixCache


@pytest.fixture(scope="module")
def model():
    cfg = reduced(ARCHS["qwen2-0.5b"])
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


PROMPTS = [
    [5, 6, 7],
    [11, 12, 13, 14, 15],
    [21],
]


def _mk(model, pr=PROMPTS, max_new=4, **sc_kw):
    cfg, params = model
    sc_kw.setdefault("max_batch", 2)
    sc_kw.setdefault("max_seq", 64)
    sc_kw.setdefault("prefill_mode", "batched")
    sc_kw.setdefault("prefill_chunk", 4)
    clock = sc_kw.pop("clock", None)
    kw = {"clock": clock} if clock is not None else {}
    eng = ServingEngine(cfg, params, ServeConfig(**sc_kw), **kw)
    reqs = [Request(prompt=list(p), max_new_tokens=max_new, rid=i)
            for i, p in enumerate(pr)]
    for r in reqs:
        eng.submit(r)
    return eng, reqs


def _run(model, **sc_kw):
    eng, reqs = _mk(model, **sc_kw)
    eng.run_to_completion()
    return eng, reqs


@pytest.fixture(scope="module")
def baseline(model):
    _, reqs = _run(model)
    return [r.out_tokens for r in reqs]


# ---------------------------------------------------------- chaos engine
def test_chaos_decision_is_pure_function_of_seed_point_draw():
    a, b = ChaosEngine(ChaosConfig(seed=7, gemm_fault=0.5)), None
    seq_a = [a.fire("substrate.dispatch") for _ in range(64)]
    # interleave other points between draws: decisions must not move
    b = ChaosEngine(ChaosConfig(seed=7, gemm_fault=0.5))
    seq_b = []
    for _ in range(64):
        b.fire("engine.sample")
        b.fire("pool.alloc")
        seq_b.append(b.fire("substrate.dispatch"))
    assert seq_a == seq_b
    assert any(seq_a) and not all(seq_a)
    # a different seed gives a different (but deterministic) sequence
    c = ChaosEngine(ChaosConfig(seed=8, gemm_fault=0.5))
    assert [c.fire("substrate.dispatch") for _ in range(64)] != seq_a


def test_chaos_at_trigger_fires_exactly_once():
    e = ChaosEngine(ChaosConfig(nan_logits_at=2))
    hits = [e.fire("engine.sample") for _ in range(6)]
    assert hits == [False, False, True, False, False, False]
    assert e.chaos_log == [("engine.sample", 2, "")]


def test_chaos_state_snapshot_roundtrip():
    e = ChaosEngine(ChaosConfig(seed=3, gemm_fault=0.5))
    pre = [e.fire("substrate.dispatch") for _ in range(10)]
    snap = e.state_snapshot()
    tail = [e.fire("substrate.dispatch") for _ in range(10)]
    e2 = ChaosEngine(ChaosEngine.config_from_snapshot(snap))
    e2.load_state(snap)
    assert [e2.fire("substrate.dispatch") for _ in range(10)] == tail
    assert pre  # silence unused warning; pre-draws exercised the counter


def test_parse_spec_roundtrip_and_errors():
    c = chaos.parse_spec("seed=3, gemm=0.05, nan_at=2, crash=0.01")
    assert c == ChaosConfig(seed=3, gemm_fault=0.05, nan_logits_at=2,
                            crash=0.01)
    assert c.without_crash().crash == 0.0
    with pytest.raises(ValueError, match="unknown chaos spec key"):
        chaos.parse_spec("bogus=1")
    with pytest.raises(ValueError, match="key=value"):
        chaos.parse_spec("seed")


def test_ambient_fire_is_noop_outside_scope():
    assert chaos.active() is None
    assert chaos.fire("engine.tick") is False
    eng = ChaosEngine(ChaosConfig(crash_at=0))
    with chaos.scope(eng):
        assert chaos.active() is eng
        assert chaos.fire("engine.tick") is True
    assert chaos.active() is None


# ----------------------------------------------------- admission control
def test_bounded_queue_rejects_overload_typed(model):
    eng, _ = _mk(model, pr=[], max_queue=2)
    eng.submit(Request(prompt=[1, 2], max_new_tokens=2, rid=0))
    eng.submit(Request(prompt=[3, 4], max_new_tokens=2, rid=1))
    r = Request(prompt=[5, 6], max_new_tokens=2, rid=2)
    with pytest.raises(AdmissionError) as ei:
        eng.submit(r)
    assert ei.value.outcome == Outcome.REJECTED_OVERLOAD
    assert r.done and r.outcome == Outcome.REJECTED_OVERLOAD.value
    assert eng.stats["outcome_rejected_overload"] == 1
    # back-compat: AdmissionError still is a ValueError
    assert isinstance(ei.value, ValueError)


def test_invalid_prompt_fails_typed(model):
    eng, _ = _mk(model, pr=[])
    r = Request(prompt=[], max_new_tokens=2, rid=0)
    with pytest.raises(ValueError):
        eng.submit(r)
    assert r.done and r.outcome == Outcome.FAILED.value


# ---------------------------------------------------------- deadlines
def test_total_deadline_expires_typed(model):
    t = [0.0]
    eng, reqs = _mk(model, deadline_ms=5.0, max_new=50,
                    clock=lambda: t[0])
    eng.step()
    t[0] = 0.001                     # 1ms < 5ms: still running
    eng.step()
    assert not all(r.done for r in reqs)
    t[0] = 10.0                      # 10s >> 5ms
    eng.step()
    assert all(r.done and r.outcome == Outcome.DEADLINE_EXPIRED.value
               for r in reqs)
    assert eng.stats["outcome_deadline_expired"] == len(reqs)


def test_ttft_deadline_only_pre_first_token(model):
    t = [0.0]
    eng, reqs = _mk(model, ttft_deadline_ms=1000.0, max_new=3,
                    clock=lambda: t[0])
    eng.run_to_completion()
    # every request got its first token instantly (fake clock never moved)
    assert all(r.outcome == Outcome.OK.value for r in reqs)


# ------------------------------------------------ NaN/Inf logit handling
def test_transient_nan_retried_stream_identical(model, baseline):
    eng, reqs = _run(model, chaos=ChaosConfig(nan_logits_at=0))
    assert [r.out_tokens for r in reqs] == baseline
    assert eng.stats["sample_retries"] == 1
    assert all(r.outcome == Outcome.OK.value for r in reqs)


def test_persistent_nan_fails_typed_no_hang(model):
    eng, reqs = _run(model, chaos=ChaosConfig(nan_logits=1.0),
                     max_retries=1)
    assert all(r.done and r.outcome == Outcome.FAILED.value for r in reqs)
    assert all("non-finite" in r.error for r in reqs)
    assert eng.stats["outcome_failed"] == len(reqs)


# --------------------------------------------------- GEMM launch faults
def test_transient_gemm_fault_retried_stream_identical(model, baseline):
    eng, reqs = _run(model, chaos=ChaosConfig(gemm_fault_at=0))
    assert [r.out_tokens for r in reqs] == baseline
    assert eng.stats["kernel_fault_retries"] >= 1
    assert all(r.outcome == Outcome.OK.value for r in reqs)


def test_persistent_gemm_fault_fails_typed_no_hang(model):
    eng, reqs = _run(model, chaos=ChaosConfig(gemm_fault=1.0))
    assert all(r.done and r.outcome == Outcome.FAILED.value for r in reqs)
    assert eng.stats["outcome_failed"] == len(reqs)


# ------------------------------------------- retries over a donated pool
@pytest.mark.parametrize("chaos_cfg, counter", [
    (ChaosConfig(nan_logits_at=0), "sample_retries"),
    (ChaosConfig(nan_logits_at=3), "sample_retries"),
    (ChaosConfig(gemm_fault_at=0), "kernel_fault_retries"),
    (ChaosConfig(gemm_fault_at=2), "kernel_fault_retries"),
], ids=["nan_first_decode", "nan_later_decode", "gemm_fault_first",
        "gemm_fault_later"])
def test_paged_retry_over_donated_pool_stream_identical(model, baseline,
                                                        chaos_cfg, counter):
    """The paged engine donates its pool to every step: a tick retried
    after non-finite logits re-dispatches from the cache the failed launch
    returned, a KernelFault retry from the pool the faulted trace never
    consumed, and both give the clean streams.  Every pool handed to a
    step was donated, and the engine ends holding a live one."""
    from _paged_pool import assert_donated, record_donations
    eng, reqs = _mk(model, kv_pages=40, page_size=8, chaos=chaos_cfg)
    given = record_donations(eng)
    eng.run_to_completion()
    assert [r.out_tokens for r in reqs] == baseline
    assert eng.stats[counter] >= 1
    assert all(r.outcome == Outcome.OK.value for r in reqs)
    assert_donated(given, eng.cache)


# --------------------------------------------- page exhaustion + watchdog
def test_page_exhaustion_chaos_terminates_all_typed(model):
    eng, reqs = _run(model, kv_pages=24, page_size=8,
                     chaos=ChaosConfig(page_exhaust=1.0),
                     watchdog_ticks=4)
    assert all(r.done and r.outcome is not None for r in reqs)
    # nothing can admit, so the watchdog must have broken the stall
    assert eng.stats["watchdog_fired"] >= 1


def test_zero_chaos_probabilities_fire_nothing(model, baseline):
    eng, reqs = _run(model, chaos=ChaosConfig(seed=123))
    assert [r.out_tokens for r in reqs] == baseline
    assert eng._chaos.chaos_log == []


# ----------------------------------------------------------- preemption
def test_preemption_streams_bit_identical(model):
    _, ample = _run(model, max_new=8, kv_pages=40, page_size=8,
                    preempt_policy="youngest", prefix_cache=True)
    eng, tight = _run(model, max_new=8, kv_pages=5, page_size=8,
                      preempt_policy="youngest", prefix_cache=True)
    assert eng.stats["preemptions"] >= 1
    assert ([r.out_tokens for r in tight]
            == [r.out_tokens for r in ample])
    preempted = [r for r in tight if r.preemptions]
    assert preempted
    assert all(r.outcome == Outcome.PREEMPTED_RETRIED.value
               for r in preempted)
    assert all(r.outcome == Outcome.OK.value
               for r in tight if not r.preemptions)


def test_preemption_matches_dense_streams(model, baseline):
    eng, reqs = _run(model, kv_pages=5, page_size=8,
                     preempt_policy="youngest", prefix_cache=True)
    assert [r.out_tokens for r in reqs] == baseline


def test_policy_none_small_pool_rejected_at_construction(model):
    cfg, params = model
    with pytest.raises(ValueError, match="preempt_policy"):
        ServingEngine(cfg, params,
                      ServeConfig(max_batch=2, max_seq=64, kv_pages=5,
                                  page_size=8))


def test_unknown_preempt_policy_rejected(model):
    cfg, params = model
    with pytest.raises(ValueError, match="preempt_policy"):
        ServingEngine(cfg, params,
                      ServeConfig(max_batch=2, max_seq=64,
                                  preempt_policy="oldest"))


# ------------------------------------------------------- crash recovery
def _finish_after_restore(model, eng, reqs, max_restarts=3):
    cfg, params = model
    restarts = 0
    while True:
        try:
            eng.run_to_completion()
            break
        except EngineCrash:
            restarts += 1
            assert restarts <= max_restarts, "crash recovery livelocked"
            snap = eng.latest_snapshot()
            assert snap is not None
            eng = ServingEngine.restore(cfg, params, eng.sc, snap)
    final = {r.rid: r for r in reqs}
    for r in eng.restored_requests:
        final[r.rid] = r
    return eng, [final[r.rid] for r in reqs], restarts


@pytest.mark.parametrize("crash_at", [0, 2, 4])
def test_crash_restore_bit_identical(model, baseline, crash_at):
    eng, reqs = _mk(model, chaos=ChaosConfig(crash_at=crash_at),
                    snapshot_every_ticks=1)
    eng, reqs, restarts = _finish_after_restore(model, eng, reqs)
    assert restarts == 1
    assert [r.out_tokens for r in reqs] == baseline
    assert all(r.outcome == Outcome.OK.value for r in reqs)


def test_crash_restore_paged_with_prefix_cache(model):
    shared = [7, 8, 9, 10, 11, 12, 13, 14]
    pr = [shared + [20 + i] for i in range(3)]
    _, clean = _run(model, pr=pr, max_new=6, kv_pages=40, page_size=8,
                    prefix_cache=True)
    eng, reqs = _mk(model, pr=pr, max_new=6, kv_pages=40, page_size=8,
                    prefix_cache=True, chaos=ChaosConfig(crash_at=3),
                    snapshot_every_ticks=1)
    eng, reqs, restarts = _finish_after_restore(model, eng, reqs)
    assert restarts == 1
    assert ([r.out_tokens for r in reqs]
            == [r.out_tokens for r in clean])


def test_restore_strips_crash_trigger_by_default(model):
    eng, reqs = _mk(model, chaos=ChaosConfig(crash_at=1),
                    snapshot_every_ticks=1)
    with pytest.raises(EngineCrash):
        eng.run_to_completion()
    cfg, params = model
    e2 = ServingEngine.restore(cfg, params, eng.sc, eng.latest_snapshot())
    assert e2.sc.chaos.crash_at == -1
    # the chaos draw counters carried over: replay continues, not restarts
    assert e2._chaos.chaos_draws["engine.tick"] >= 1


def test_snapshot_without_crash_chaos_is_inert(model, baseline):
    eng, reqs = _run(model, snapshot_every_ticks=2)
    assert [r.out_tokens for r in reqs] == baseline
    assert eng.stats["snapshots_taken"] >= 1


# --------------------------------------------- pool/radix snapshot bits
def test_radix_snapshot_roundtrip_preserves_matches():
    pool = PagePool(16, 4)
    rad = RadixCache(4)
    toks = list(range(12))
    pages = pool.alloc(3)
    rad.insert(toks, pages, pool)
    rad2 = RadixCache.from_snapshot(rad.to_snapshot())
    assert rad2.match(toks) == rad.match(toks)
    assert rad2.n_pages() == rad.n_pages()
    assert rad2.n_nodes() == rad.n_nodes()
    # eviction on the restored tree releases the same pages
    pool2 = PagePool(16, 4)
    pool2.free_pages[:] = list(pool.free_pages)
    pool2.refcounts[:] = list(pool.refcounts)
    for pg in pages:
        pool.decref(pg)
        pool2.decref(pg)          # drop producer refs; tree ref remains
    assert rad.evict(3, pool) == rad2.evict(3, pool2) == 3
    assert pool.free_pages == pool2.free_pages


# ------------------------------------------------- outcome bookkeeping
def test_every_request_counted_exactly_once(model):
    eng, reqs = _run(model, chaos=ChaosConfig(seed=5, nan_logits=0.3,
                                              gemm_fault=0.1),
                     max_retries=1)
    assert all(r.done and r.outcome is not None for r in reqs)
    counted = sum(v for k, v in eng.stats.items()
                  if k.startswith("outcome_"))
    assert counted == len(reqs)


def test_hardened_defaults_keep_pr7_config_shape(model):
    """Default ServeConfig must not enable any resilience feature: the
    fault-free fast path is the PR7 engine bit-for-bit."""
    sc = ServeConfig(max_batch=2, max_seq=64)
    assert sc.max_queue == 0 and sc.deadline_ms == 0.0
    assert sc.preempt_policy == "none" and sc.chaos is None
    assert sc.snapshot_every_ticks == 0
    assert dataclasses.fields(sc)  # it stayed a dataclass
