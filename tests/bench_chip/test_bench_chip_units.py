"""CPU tests of the chip benchmark's yardstick: BENCHMARK.json against its
contract, the traffic generator, the FLOP and byte counts, the seeded
weights, and the trace reduction on a synthetic trace."""
import json
import math
import re
from pathlib import Path

import numpy as np
import pytest

from conftest import BENCH, REPO, load_bench

from chipbench import reference, roofline, traffic, weights, xtrace

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_names_files_and_metrics():
    b = load_bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["command"][1].startswith(b["paths"][0] + "/")
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    configs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        conf = json.loads((REPO / c["file"]).read_text())
        assert conf["name"] == c["name"] and conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]
        assert all(k in conf["published"] for k in c["reduced"])
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    for w in b["workloads"]:
        assert w["config"] in configs and w["chips"] == 1
        assert (BENCH / "traffic" / f"{w['traffic']}.json").exists()
        mine = {m["name"] for m in b["end_to_end"]
                if w["name"] in m.get("workloads", [w["name"]])}
        assert "setup_s" in mine and len(mine) >= 2
        layer = [m for m in b["per_layer"] if w["name"] in m["workloads"]]
        assert layer and all(m["moves"] in mine for m in layer)
    for m in b["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").exists()
        assert m["moves"] in e2e
        if m["name"].split(".")[0].endswith("_roofline"):
            assert m["unit"] == "%"


@pytest.mark.parametrize("mix", sorted({w["traffic"] for w in
                                        load_bench()["workloads"]}))
def test_traffic_is_the_same_work_for_every_seed(mix):
    m = json.loads((BENCH / "traffic" / f"{mix}.json").read_text())
    e = m["engine"]
    a = traffic.generate(m, 2**31 + 5, 40, 1000, e["max_seq"])
    a2 = traffic.generate(m, 2**31 + 5, 40, 1000, e["max_seq"])
    b = traffic.generate(m, 17, 40, 1000, e["max_seq"])
    assert [(i.due, i.prompt, i.max_new) for i in a] == \
        [(i.due, i.prompt, i.max_new) for i in a2]
    assert [i.prompt for i in a] != [i.prompt for i in b]
    for items in (a, b):
        assert all(len(i.prompt) + i.max_new <= e["max_seq"] - 1
                   and 0 <= min(i.prompt) and max(i.prompt) < 1000
                   for i in items)
    if m["kind"] == "sessions":
        # the same arrival times: every seed puts as many requests due in
        # the window, and only which session takes which slot differs
        def opens(items):
            first = {}
            for i in items:
                first.setdefault(i.session, i.due)
            return sorted(first.values())
        assert opens(a) == opens(b)
        assert sum(i.due >= 0 for i in a) == sum(i.due >= 0 for i in b)
    if m["kind"] == "batch":
        # the same sizes in the same order: only the token ids differ
        assert [(len(i.prompt), i.max_new) for i in a] == \
            [(len(i.prompt), i.max_new) for i in b]
        med = np.median([len(i.prompt) for i in a])
        assert abs(math.log(med / m["prompt_tokens"]["median"])) < 0.15
        med = np.median([i.max_new for i in a])
        assert abs(math.log(med / m["max_new_tokens"]["median"])) < 0.2


def test_chat_sessions_rate_sharing_and_lengths():
    m = json.loads((BENCH / "traffic" / "chat.json").read_text())
    seconds = 40
    items = traffic.generate(m, 99, seconds, 151936, 4096)
    span = m["lead_in_s"] + seconds
    starts = {}
    for it in items:
        starts.setdefault(it.session, it.due)
    # first turns: the sessions that open inside the span, at the rate
    assert len(starts) <= round(m["rate"] * span)
    assert len(starts) >= 0.9 * round(m["rate"] * span)
    assert min(starts.values()) == -m["lead_in_s"]
    # every prompt opens with one of the shared system prompts
    n_sys = m["system_prompts"]["tokens"]
    heads = [tuple(it.prompt[:n_sys]) for it in items]
    assert len(set(heads)) <= m["system_prompts"]["count"]
    # turn k+1 extends turn k
    by_s = {}
    for it in sorted(items, key=lambda i: i.due):
        prev = by_s.get(it.session)
        if prev is not None:
            assert it.prompt[:len(prev.prompt)] == prev.prompt
            assert 5 <= it.due - prev.due <= 15
            assert len(it.prompt) > len(prev.prompt) + prev.max_new
        by_s[it.session] = it
    # the gaps and the Zipf shares come from the shape seed, not the seed
    other = traffic.generate(m, 12345, seconds, 151936, 4096)
    assert sorted(len(i.prompt) for i in items if i.due < 0) != [] and \
        len(other) > 0
    top = max(set(heads), key=heads.count)
    share = heads.count(top) / len(heads)
    p1 = 1 / sum(1 / k ** 1.2 for k in range(1, 9))
    assert 0.5 * p1 < share < 1.6 * p1


QWEN2 = dict(layers=24, hidden=896, heads=14, kv_heads=2, head_dim=64,
             ffn=4864, vocab=151936)
QWEN25 = dict(layers=6, hidden=5120, heads=40, kv_heads=8, head_dim=128,
              ffn=13824, vocab=38016)


def test_site_flops_and_bytes_by_hand():
    # qwen2-0.5b attn.wq at 4 decode rows: (4, 896) @ (896, 896), bf16
    f, b = roofline.site_cost(QWEN2, "attn.wq", 4)
    assert f == 2 * 4 * 896 * 896
    assert b == 2 * (4 * 896 + 896 * 896) + 2 * 4 * 896
    # qwen2.5-14b mlp.wi (gate and up) at 12 x 256 prefill rows
    f, b = roofline.site_cost(QWEN25, "mlp.wi", 3072)
    assert f == 2 * 2 * 3072 * 5120 * 13824
    assert b == 2 * (3072 * 5120 + 2 * 5120 * 13824) + 2 * 3072 * 13824
    # qwen2.5-14b qk for one row of 256 queries over 1000 live keys:
    # 8 kv heads x (5*256, 128) @ (128, 1000), fp32 scores
    f, b = roofline.site_cost(QWEN25, "attn.qk", 0, [(256, 1000)])
    assert f == 8 * 2 * (5 * 256) * 128 * 1000
    assert b == 8 * (2 * (5 * 256 * 128 + 128 * 1000) + 4 * 5 * 256 * 1000)
    # tied head: (rows, 896) @ (896, 151936) with fp32 logits
    f, b = roofline.site_cost(QWEN2, "unembed", 2)
    assert f == 2 * 2 * 896 * 151936
    # one decode token at context 100: 2 x matmul params x layers,
    # 4 x heads x head_dim x context x layers of attention, and the logits
    per_layer = 896 * 896 + 2 * 896 * 128 + 896 * 896 + 3 * 896 * 4864
    want = (2 * per_layer * 24 + 4 * 14 * 64 * 100 * 24
            + 2 * 896 * 151936)
    assert roofline.model_flops(QWEN2, [], [100]) == want
    # a prefill chunk of 3 tokens at positions 5, 6, 7: contexts 6, 7, 8
    got = roofline.model_flops(QWEN2, [(5, 3)], [])
    assert got == 3 * 2 * per_layer * 24 + 4 * 14 * 64 * 24 * (6 + 7 + 8)


def test_peak_table_is_keyed_by_device_kind(tmp_path):
    p = roofline.peaks("TPU v5 lite", BENCH / "peaks.json")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in p["source"]
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary", BENCH / "peaks.json")


def test_weights_each_layer_matches_its_stacked_slice():
    import jax
    dims = dict(layers=3, hidden=32, heads=4, kv_heads=2, head_dim=8,
                ffn=48, vocab=100)
    root = weights.root_key(2**33 + 9)
    stacked = jax.jit(jax.vmap(lambda l: weights.layer_weights(
        root, dims, l)))(jax.numpy.arange(3))
    for li in range(3):
        one = weights.layer_weights(root, dims, li)
        for k in one:
            np.testing.assert_allclose(np.asarray(stacked[k][li]),
                                       np.asarray(one[k]), rtol=1e-6,
                                       atol=1e-7)
    top = weights.top_weights(root, dims, 128, tied=False)
    assert np.all(np.asarray(top["embed/table"][100:]) == 0)
    assert np.all(np.asarray(top["lm_head/w"][:, 100:]) == 0)


def test_reference_blocks_cover_every_row_once():
    lengths = [300, 40, 2000, 257, 256, 1, 900, 900, 900]
    blocks = reference._blocks(lengths, 2048)
    seen = sorted(i for idx, _, _ in blocks for i in idx)
    assert seen == list(range(len(lengths)))
    for idx, n, L in blocks:
        assert L % 256 == 0 and all(lengths[i] <= L for i in idx)
        assert n & (n - 1) == 0 and len(idx) <= n
        assert n * L <= 2048 or n == 1


def test_reference_gaps_do_not_depend_on_the_blocking():
    """The same served tokens give the same gaps whether the rows run in
    one block or one row per block; a served token that is the
    reference's own best reads a gap of 0, another token more, and a
    token outside the vocabulary inf."""
    dims = dict(layers=2, hidden=32, heads=4, kv_heads=2, head_dim=8,
                ffn=48, vocab=100)
    rng = np.random.default_rng(0)
    served = [(rng.integers(0, 100, n).tolist(), rng.integers(0, 100, m)
               .tolist()) for n, m in ((5, 3), (40, 9), (300, 4), (2, 2))]
    kw = dict(eps=1e-6, theta=1e4, padded_vocab=128, tied=False)
    one = reference.served_gaps(dims, 5, served, budget=1 << 14, **kw)
    many = reference.served_gaps(dims, 5, served, budget=1, chunk=4, **kw)
    assert [len(g) for g in one] == [3, 9, 4, 2]
    for a, b in zip(one, many):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    assert all(np.all(g >= 0) for g in one)
    # the reference's own best token at a position reads 0, and fed back
    # it reads 0 again; a token outside the vocabulary reads inf
    p = served[1][0]
    row = np.concatenate(reference.served_gaps(
        dims, 5, [(p, [t]) for t in range(100)], **kw))
    best = int(np.argmin(row))
    assert row[best] == 0.0 and np.sum(row == 0.0) == 1
    got = reference.served_gaps(dims, 5, [(p, [best, 100])], **kw)[0]
    assert got[0] == 0.0 and np.isinf(got[1])


def _synthetic_trace():
    ms = 1e6
    return {
        "host": [("bench.traced", 0.0, 100 * ms),
                 ("bench.step", 0.0, 40 * ms),
                 ("bench.tokens", 40 * ms, 5 * ms),
                 ("bench.sleep", 45 * ms, 40 * ms),
                 ("bench.step", 85 * ms, 15 * ms)],
        "devices": {0: [("_gemm.3", 2 * ms, 10 * ms),
                        ("fusion.1", 8 * ms, 20 * ms),     # overlaps
                        ("_expert_gemm.1", 30 * ms, 5 * ms),
                        ("_gemm.3", 90 * ms, 20 * ms),     # runs past the end
                        ("copy.2", -5 * ms, 6 * ms)]},    # starts before
    }


def test_trace_reduction_on_a_synthetic_trace():
    red = xtrace.reduce(_synthetic_trace(), r"^_(expert_)?gemm(\.|$)")
    assert red["window_s"] == pytest.approx(0.1)
    # busy: [0,1] + [2,28] + [30,35] + [90,100] ms
    assert red["busy_s"] == pytest.approx((1 + 26 + 5 + 10) * 1e-3)
    assert red["kernel_calls"] == 3
    assert red["kernel_s"] == pytest.approx((10 + 5 + 10) * 1e-3)
    gaps = red["idle_gaps"]
    assert gaps[0][0] == "bench.sleep"                 # 35..90, mid in sleep
    assert gaps[0][1] == pytest.approx(0.055)
    assert {g[0] for g in gaps[1:]} == {"bench.step"}
    assert red["device_ops"][0][0] == "_gemm.3"


FIXTURE = Path(__file__).parent / "data" / "qwen2-0.5b.chat.xplane.pb"
FIXTURE_FACTS = Path(__file__).parent / "data" / "qwen2-0.5b.chat.facts.json"


def test_trace_reduction_on_a_recorded_chip_trace():
    """A short traced interval of qwen2-0.5b.chat recorded on a TPU v5e.
    The facts were read from the raw trace by hand: the window is the
    ``bench.traced`` span, the two dispatches hold 2 x (8 x 24 + 1) kernel
    events, and the costliest op outside the loops is the decode's page
    extraction (``fusion.109``)."""
    from chipbench import harness
    facts = json.loads(FIXTURE_FACTS.read_text())
    tr = xtrace.load(str(FIXTURE))
    assert sorted(tr["devices"]) == [0]
    red = xtrace.reduce(tr, harness.KERNEL_EVENT)
    assert red["window_s"] == pytest.approx(facts["window_s"], rel=1e-6)
    assert red["busy_s"] == pytest.approx(facts["busy_s"], rel=1e-6)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["kernel_calls"] == facts["dispatches"] * (8 * 24 + 1)
    assert 0 < red["kernel_s"] < red["busy_s"]
    assert red["device_ops"][0][0] == facts["top_op"]
    assert red["device_ops"][0][1] == pytest.approx(facts["top_op_s"],
                                                    rel=1e-6)
    assert not any(n.split(" ")[1] == "while" for n, _ in red["device_ops"])
    assert all(g[0].startswith("bench.") or g[0] == "untraced"
               for g in red["idle_gaps"])
    assert red["idle_gaps"][0][1] == pytest.approx(facts["longest_gap_s"],
                                                   rel=1e-6)
