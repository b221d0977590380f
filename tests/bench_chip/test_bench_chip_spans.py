"""The per-layer metrics read from the serving engine's spans: their
arithmetic on counter deltas, silence where a program keeps no such counter,
and CPU runs of the tiny cells in which a traced run reports them."""
import copy
import json
from types import SimpleNamespace

import pytest

from conftest import load_bench, run_tiny

from chipbench import spans

TINY_OFFLINE = {
    "kind": "batch", "requests": 60,
    "prompt_tokens": {"median": 12, "sigma": 0.5, "max": 40},
    "max_new_tokens": {"median": 24, "sigma": 0.5, "max": 48},
    "shape_seed": 0, "lead_in_s": 0.5,
    "engine": {"max_batch": 4, "prefill_chunk": 16, "max_seq": 128,
               "kv_pages": 40, "page_size": 16, "prefix_cache": True},
}


def _ctx(**stats):
    return SimpleNamespace(stats=stats)


def test_span_readings_are_counter_ratios_in_ms():
    c = _ctx(first_token_s=1.5, first_tokens=3, sample_time_s=0.02,
             decode_dispatches=4, step_time_s=2.0, steps=5,
             prefill_wait_s=0.6, decode_wait_s=0.9)
    assert spans.first_token_ms(c) == pytest.approx(500.0)
    assert spans.sample_ms(c) == pytest.approx(5.0)
    assert spans.engine_host_ms(c) == pytest.approx(100.0)


@pytest.mark.parametrize("stats", [
    # a program older than the engine's spans keeps none of the counters
    dict(decode_dispatches=4, decode_time_s=0.4, prefill_time_s=0.2),
    # an interval with no first token, no decode dispatch and no tick
    dict(first_token_s=0.0, first_tokens=0, sample_time_s=0.0,
         decode_dispatches=0, step_time_s=0.0, steps=0, prefill_wait_s=0.0,
         decode_wait_s=0.0),
], ids=["no-counters", "no-events"])
def test_span_readings_are_silent_without_events(stats):
    c = _ctx(**stats)
    assert spans.first_token_ms(c) is None
    assert spans.sample_ms(c) is None
    assert spans.engine_host_ms(c) is None


def test_traced_tiny_chat_reports_the_span_metrics(tiny_home):
    home, bench = tiny_home
    r = run_tiny(home, bench, seed=3000000041, trace=True, trace_s=1.5)
    m = r["metrics"]
    for name in ("first_token_ms.ttft", "sample_ms.itl",
                 "engine_host_ms.itl"):
        assert m[name]["value"] > 0 and m[name]["unit"] == "ms", name
    assert "sample_ms.tput" not in m and "engine_host_ms.tput" not in m


def test_traced_tiny_offline_reports_the_span_metrics(tiny_home):
    from chipbench import harness
    home, bench = tiny_home
    (home / "traffic" / "toffline.json").write_text(json.dumps(TINY_OFFLINE))
    bench = copy.deepcopy(bench)
    bench["workloads"].append({"name": "tiny.offline", "config": "tiny",
                               "traffic": "toffline", "chips": 1,
                               "why": "test"})
    real = {m["name"]: m for m in load_bench()["end_to_end"]
            + load_bench()["per_layer"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "qwen2-0.5b.offline" in real[m["name"]].get("workloads", []):
            m["workloads"] = m["workloads"] + ["tiny.offline"]
    import time
    r = harness.run_cell(bench, "tiny.offline", 3000000043, 4.0, True,
                         t_process=time.perf_counter(), root=home, home=home,
                         require_chip=False, trace_s=1.5)
    m = r["metrics"]
    for name in ("sample_ms.tput", "engine_host_ms.tput"):
        assert m[name]["value"] > 0 and m[name]["unit"] == "ms", name
    assert "first_token_ms.ttft" not in m
