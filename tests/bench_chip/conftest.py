"""Fixtures for the chip benchmark's CPU tests: the benchmark's library on
the path, and a tiny benchmark home (configuration, mix and metric files
beside a BENCHMARK.json) that a test run on the CPU can hold."""
import copy
import json
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmarks" / "chip"
sys.path.insert(0, str(BENCH))

TINY_SIZES = dict(hidden_size=64, intermediate_size=128,
                  num_attention_heads=4, num_key_value_heads=2,
                  num_hidden_layers=2, vocab_size=32768)
TINY_CHAT = {
    "kind": "sessions", "rate": 4.0, "arrival_cv": 2.0,
    "system_prompts": {"count": 3, "tokens": 40, "zipf_s": 1.2},
    "turns": {"mean": 2, "max": 3}, "think_s": [0.3, 0.8],
    "user_tokens": {"median": 10, "sigma": 0.5, "max": 40},
    "max_new_tokens": {"median": 24, "sigma": 0.5, "max": 48},
    "session_max_tokens": 128, "shape_seed": 0, "lead_in_s": 0.5,
    "drain_s": 20,
    "engine": {"max_batch": 4, "prefill_chunk": 16, "max_seq": 128,
               "kv_pages": 40, "page_size": 16, "prefix_cache": True},
}


def load_bench():
    return json.loads((REPO / "BENCHMARK.json").read_text())


def mirror(home, rel):
    """Copy the checkout's file or directory ``rel`` to the same place under
    ``home``, so that a configuration's ``reference`` path resolves there."""
    src, dst = REPO / rel, home / rel
    dst.parent.mkdir(parents=True, exist_ok=True)
    if src.is_dir():
        shutil.copytree(src, dst)
    else:
        shutil.copy(src, dst)


@pytest.fixture
def tiny_home(tmp_path, monkeypatch):
    """A benchmark home with one tiny cell, ``tiny.chat``, served by the
    ``xla`` backend in float32 (the ``arrayflex`` kernel runs interpreted
    on the CPU, which is slow); its metric files are the benchmark's own.
    At this size the widest gap of a bf16 run and of a w8a8 run overlap,
    so the tiny cell states float32, whose control is bf16.  Over 16 seeds
    of a 4 s run the mean gap of sound runs read at most 1.04e-5 and of the
    control at least 2.33e-5; the limit lies between, so no seed of the
    tests is pinned to a side of it (PERF.md)."""
    # keep compiled programs out of the checkout's cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jaxc"))
    for d in ("configs", "traffic"):
        (tmp_path / d).mkdir()
    shutil.copytree(BENCH / "metrics", tmp_path / "metrics")
    mirror(tmp_path, "benchmarks/chip/archs")
    shutil.copy(BENCH / "peaks.json", tmp_path / "peaks.json")
    conf = json.loads((BENCH / "configs" / "qwen2-0.5b.json").read_text())
    conf.update(TINY_SIZES)
    conf["program"].update(gemm_backend="xla", compute_dtype="float32")
    conf["correct"] = {"logit_gap_mean": 1.6e-5, "tokens_checked_min": 200}
    (tmp_path / "configs" / "tiny.json").write_text(json.dumps(conf))
    (tmp_path / "traffic" / "tchat.json").write_text(json.dumps(TINY_CHAT))
    bench = copy.deepcopy(load_bench())
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "configs/tiny.json", "reduced": [],
                         "why": "test"}]
    bench["workloads"] = [{"name": "tiny.chat", "config": "tiny",
                           "traffic": "tchat", "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = (["tiny.chat"] if "qwen2-0.5b.short-burst" in
                              m["workloads"] else [])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path, bench


def run_tiny(home, bench, seed=3000000007, seconds=4.0, trace=False,
             **kw):
    import time
    from chipbench import harness
    return harness.run_cell(bench, "tiny.chat", seed, seconds, trace,
                            t_process=time.perf_counter(), root=home,
                            home=home, require_chip=False, **kw)
