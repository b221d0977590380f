"""The serving engine's host spans and the counters they feed.

Every engine tick runs in an ``engine.step`` span that holds ``engine.admit``,
``engine.{prefill,decode}.{prepare,launch,wait}``, ``engine.sample`` and
``engine.commit``.  Each span is a profiler ``TraceAnnotation`` (so it lands
in the xplane host plane on the device trace's clock) and, where it feeds a
counter, adds its duration on the engine's own clock.  Under a fake clock that
advances a fixed step per read the counter identities hold exactly; the spans
add no device sync; the jitted steps carry their functions' names.
"""
import glob
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS, reduced
from repro.models import lm
from repro.serving import ServeConfig, ServingEngine
from repro.serving.engine import Request

PROMPTS = [[5, 6, 7], [11, 12, 13, 14, 15, 16, 17, 18, 19], [21],
           [31, 32, 33, 34, 35]]
TICK_SPANS = ("engine.admit", "engine.prefill.prepare",
              "engine.prefill.launch", "engine.prefill.wait",
              "engine.decode.prepare", "engine.decode.launch",
              "engine.decode.wait", "engine.sample", "engine.commit")


@pytest.fixture(scope="module")
def model():
    cfg = reduced(ARCHS["qwen2-0.5b"])
    return cfg, lm.init_params(cfg, jax.random.PRNGKey(0))


class StepClock:
    """Advances ``dt`` on every read: a span that reads it twice with no
    read in between lasts exactly ``dt``."""

    def __init__(self, dt=1.0):
        self.t, self.dt = 0.0, dt

    def __call__(self):
        self.t += self.dt
        return self.t


def _engine(model, paged=True, clock=None, **kw):
    cfg, params = model
    sc = dict(max_batch=2, max_seq=64, prefill_mode="batched",
              prefill_chunk=4)
    if paged:
        sc.update(kv_pages=40, page_size=8)
    sc.update(kw)
    return ServingEngine(cfg, params, ServeConfig(**sc),
                         clock=clock or StepClock())


def _submit(eng, prompts=PROMPTS, max_new=3):
    reqs = [Request(prompt=list(p), max_new_tokens=max_new, rid=i)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    return reqs


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "dense"])
def test_counter_identities_under_a_step_clock(model, paged):
    eng = _engine(model, paged, clock=StepClock(1.0))
    reqs = _submit(eng)
    ticks = eng.run_to_completion()
    st = eng.stats
    assert st["prefill_dispatches"] > 0 and st["decode_dispatches"] > 0
    # each dispatch is its launch span (two reads, none between) + its wait
    assert (st["prefill_time_s"] - st["prefill_wait_s"]
            == 1.0 * st["prefill_dispatches"])
    assert (st["decode_time_s"] - st["decode_wait_s"] - st["sample_time_s"]
            == 1.0 * st["decode_dispatches"])
    assert st["prefill_wait_s"] == 1.0 * st["prefill_dispatches"]
    assert st["decode_wait_s"] == st["sample_time_s"] \
        == 1.0 * st["decode_dispatches"]
    assert st["step_time_s"] >= st["prefill_time_s"] + st["decode_time_s"]
    assert st["steps"] == ticks
    assert st["admissions"] == len(reqs)
    assert st["first_tokens"] == sum(bool(r.out_tokens) for r in reqs) \
        == len(reqs)
    assert all(r.ttft_s == r.t_first_token - r.t_submit > 0 for r in reqs)
    assert all(r.t_submit < r.t_admit < r.t_first_token for r in reqs)


def test_heartbeat_takes_the_step_span(model):
    eng = _engine(model, clock=StepClock(1.0))
    _submit(eng)
    spans, step = [], eng.step

    def recorded():
        before = eng.stats["step_time_s"]
        out = step()
        spans.append(eng.stats["step_time_s"] - before)
        return out

    eng.step = recorded
    eng.run_to_completion()
    assert eng.monitor.beats[0].step_time_s == spans[-1] > 0


def test_queue_wait_and_ttft_run_from_enqueue(model):
    now = [0.0]
    eng = _engine(model, clock=lambda: now[0])
    [req] = _submit(eng, [[5, 6, 7]], max_new=2)
    now[0] = 5.0
    eng.step()
    assert eng.stats["queue_wait_s"] == 5.0
    assert eng.stats["admissions"] == 1
    # a 3-token prompt is prefilled and decoded in its admission tick:
    # TTFT counts the queue wait, the admission-to-token time does not
    assert req.ttft_s == 5.0
    assert eng.stats["first_token_s"] == 0.0
    assert eng.stats["first_tokens"] == 1


def test_requeued_request_waits_from_its_requeue(model):
    eng = _engine(model, max_seq=64, kv_pages=5, page_size=8,
                  preempt_policy="youngest", prefix_cache=True)
    reqs = _submit(eng, [[5, 6, 7], [11, 12, 13, 14, 15], [21]], max_new=8)
    eng.run_to_completion()
    st = eng.stats
    assert st["preemptions"] >= 1
    assert st["admissions"] == len(reqs) + st["preemptions"]
    assert st["first_tokens"] == len(reqs)
    assert st["queue_wait_s"] > 0


def test_spans_add_no_device_sync(model, monkeypatch):
    calls = []
    real = jax.block_until_ready

    def counting(x):
        calls.append(1)
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", counting)
    eng = _engine(model)
    _submit(eng)
    eng.run_to_completion()
    assert len(calls) == eng.stats["prefill_dispatches"]


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    [path] = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                    "*.xplane.pb"))
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:CPU")
            for line in plane.lines for e in line.events
            if e.name.startswith("engine.")]


def test_spans_land_in_the_profiler_trace_in_tick_order(model, tmp_path):
    eng = _engine(model)
    _submit(eng)
    eng.step()                              # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        ticks = eng.run_to_completion()
    events = _host_events(str(tmp_path))
    steps = sorted(e for e in events if e[0] == "engine.step")
    assert len(steps) == ticks
    inner = [e for e in events if e[0] != "engine.step"]
    assert {e[0] for e in inner} == set(TICK_SPANS)
    for name, s, e in inner:
        assert sum(s0 <= s and e <= e0 for _, s0, e0 in steps) == 1, name
    rank = {n: i for i, n in enumerate(TICK_SPANS)}
    for _, s0, e0 in steps:
        names = [n for n, s, _ in sorted(
            (x for x in inner if s0 <= x[1] <= e0), key=lambda x: x[1])]
        assert [rank[n] for n in names] == sorted(rank[n] for n in names), \
            names


def test_jitted_steps_carry_their_names(model):
    eng = _engine(model)
    B, n_pg = eng.sc.max_batch, eng.pages_per_seq
    i32 = jnp.int32
    bt = jnp.zeros((B, n_pg), i32)
    vec = jnp.zeros(B, i32)
    dec = eng._decode_paged.lower(eng.params, eng.cache, vec, vec, bt)
    pre = eng._prefill_paged.lower(eng.params, eng.cache,
                                   jnp.zeros((B, eng.prefill_chunk), i32),
                                   vec, vec, bt)
    assert dec.as_text().startswith("module @jit_decode_step_paged ")
    assert pre.as_text().startswith("module @jit_prefill_step_paged ")
