"""The Qwen3-MoE architecture module (``archs/qwen3_moe.py``) on the CPU: a
tiny configuration of it, holding 4 of 8 experts from the third, runs
``correct`` through the harness as new files and an entry; a program
whose expert layer is broken, or computes in bf16, reads above the tiny
limit; and the module's counts are pinned against a hand count at the
published widths.

The tiny configuration routes each token to all 8 experts.  With fewer,
the K/V pool's bf16 rounding flips which experts a token picks, and at
this size a flip moves the logits as much as bf16 compute does: over
seeds 11-16 at top-2 the mean gap of float32 runs read 0.00028-0.013 and
of bf16 runs 0.0065-0.023.  At top-8 of 8 the routing is the softmax
weights alone; over 16 seeds each the mean gap of float32 runs read at
most 8.44e-5 and of bf16 runs at least 2.15e-4, and the limit 1.3e-4 lies
between (PERF.md).  Top-k selection and the renormalisation are tested on
the program's side (``tests/test_moe_serving.py``)."""
import json
import time

import pytest

from conftest import BENCH, REPO

from chipbench.harness import load_arch

ARCH = "benchmarks/chip/archs/qwen3_moe.py"
# Qwen3-MoE's keys at a tiny size: 4 of 8 experts held, from expert 2
TINY_MOE = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
                head_dim=16, moe_intermediate_size=32, num_experts=4,
                num_experts_per_tok=8, num_hidden_layers=2,
                vocab_size=32768)
TINY_MOE_LIMIT = 1.3e-4


def _add_moe_cell(home, bench):
    """A tiny Qwen3-MoE configuration and a cell ``tiny-moe.chat`` on the
    tiny chat mix that reports what tiny.chat does."""
    conf = json.loads((BENCH / "configs" / "qwen3-moe-30b-a3b.json")
                      .read_text())
    conf.update(TINY_MOE, name="tiny-moe")
    conf["published"] = dict(conf["published"], num_experts=8)
    conf["program"].update(gemm_backend="xla", compute_dtype="float32",
                           expert_first=2)
    conf["correct"] = {"logit_gap_mean": TINY_MOE_LIMIT,
                       "tokens_checked_min": 200}
    (home / "configs" / "tiny-moe.json").write_text(json.dumps(conf))
    bench["configs"].append({"name": "tiny-moe", "source": "test",
                             "file": "configs/tiny-moe.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-moe.chat", "config": "tiny-moe",
                               "traffic": "tchat", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tiny.chat" in m.get("workloads", []):
            m["workloads"].append("tiny-moe.chat")
    return "tiny-moe.chat"


def _run(home, bench, cell, seed, **kw):
    from chipbench import harness
    return harness.run_cell(bench, cell, seed, 4.0, False,
                            t_process=time.perf_counter(), root=home,
                            home=home, require_chip=False, **kw)


def test_tiny_qwen3_moe_runs_correct(tiny_home):
    home, bench = tiny_home
    cell = _add_moe_cell(home, bench)
    r = _run(home, bench, cell, 3000000013)
    assert r["correct"] is True, r["compared"]
    assert r["compared"]["tokens_checked"]["value"] >= 200
    assert r["failed"] == 0


def _wrong_held_range(monkeypatch):
    """The layer computes experts 0-3 where it holds 2-5."""
    from repro.nn import moe
    serve = moe.moe_serve
    monkeypatch.setattr(moe, "moe_serve",
                        lambda p, x, first=0, **kw: serve(p, x, first=0, **kw))


def _routes_over_held_only(monkeypatch):
    """The router scores only the held experts, so their weights no longer
    share the softmax with the other chips' experts."""
    from repro.nn import moe
    serve = moe.moe_serve

    def held_only(p, x, first=0, top_k=1, **kw):
        n = p["wi_gate"].shape[0]
        return serve(dict(p, router=p["router"][:, first:first + n]), x,
                     first=0, top_k=min(top_k, n), **kw)
    monkeypatch.setattr(moe, "moe_serve", held_only)


@pytest.mark.parametrize("fault", [_wrong_held_range, _routes_over_held_only],
                         ids=["wrong_held_range", "routes_over_held_only"])
def test_a_broken_expert_layer_is_not_correct(tiny_home, monkeypatch,
                                              fault):
    home, bench = tiny_home
    cell = _add_moe_cell(home, bench)
    fault(monkeypatch)
    r = _run(home, bench, cell, 81)
    assert r["correct"] is False
    c = r["compared"]
    assert c["logit_gap_mean"]["value"] > c["logit_gap_mean"]["limit"]


def test_the_lower_precision_control_is_not_correct(tiny_home):
    home, bench = tiny_home
    cell = _add_moe_cell(home, bench)
    r = _run(home, bench, cell, 82, overrides={"compute_dtype": "bfloat16"})
    assert r["correct"] is False
    c = r["compared"]
    assert c["logit_gap_mean"]["value"] > c["logit_gap_mean"]["limit"]


# the published widths at the cell's cut: 12 layers, 16 of 128 experts
CONF = json.loads((BENCH / "configs" / "qwen3-moe-30b-a3b.json").read_text())


def test_counts_match_a_hand_count():
    m = load_arch(REPO / ARCH)
    dims = m.dims(CONF)
    assert (dims["experts"], dims["held"], dims["top_k"], dims["ffn"],
            dims["layers"], dims["vocab"]) == (128, 16, 8, 768, 12, 18992)
    # ten kernels a layer (six attention, the router, three expert banks)
    # and the head
    assert m.kernel_calls_per_dispatch(dims) == 10 * 12 + 1
    # one decode token at context 100 with 40 (token, held expert) pairs
    # counted over its 12 layers
    proj = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048 + 2048 * 128
    want = (2 * proj * 12 + 4 * 32 * 128 * 100 * 12 + 2 * 2048 * 18992
            + 40 * 6 * 2048 * 768)
    assert m.model_flops(dims, [], [100], {"moe_assignments": 40}) == want
    # a prefill chunk of 3 tokens at positions 5, 6, 7 and no routed pair
    got = m.model_flops(dims, [(5, 3)], [], {"moe_assignments": 0})
    assert got == 3 * 2 * proj * 12 + 4 * 32 * 128 * 12 * (6 + 7 + 8)
    # an expert site over 64 dispatched rows: 64 x 8 x 16 / 128 = 64
    # routed rows, each held expert's bank read once
    f, b = m.site_cost(dims, "moe.wi_gate", 64)
    assert f == 2 * 64 * 2048 * 768
    assert b == 2 * (64 * 2048 + 16 * 2048 * 768 + 64 * 768)
    # the router in float32 over all 128 experts
    f, b = m.site_cost(dims, "moe.router", 64)
    assert (f, b) == (2 * 64 * 2048 * 128,
                      4 * (64 * 2048 + 2048 * 128 + 64 * 128))
