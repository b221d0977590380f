"""CPU runs of the chip benchmark's harness on a tiny cell: a sound run is
correct and reports its metrics, a traced run reads its per-layer metrics,
a configuration, mix, metric and architecture added as new files are found
without an edit, a module that disagrees with the program's parameter tree
is refused, and a host with no chip gets no result."""
import io
import json
import time
from contextlib import redirect_stdout

import pytest

from conftest import REPO, mirror, run_tiny

NOBIAS = "tests/bench_chip/arch_qwen2_nobias.py"


def test_tiny_cell_runs_correct_with_its_end_to_end_metrics(tiny_home):
    home, bench = tiny_home
    r = run_tiny(home, bench)
    assert r["correct"] is True, r["compared"]
    assert set(r["metrics"]) == {"ttft_p90_ms", "itl_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["attempted"] >= 5 and r["failed"] == 0
    assert list(r)[-1] == "compared"
    c = r["compared"]
    assert c["tokens_checked"]["value"] >= 200
    assert c["logit_gap_mean"]["value"] <= c["logit_gap_mean"]["limit"]
    json.dumps(r, allow_nan=False)


def test_new_files_are_found_and_a_traced_run_reads_them(tiny_home):
    home, bench = tiny_home
    # a new metric is one file and one entry; nothing else changes
    (home / "metrics" / "decode_rows.itl.py").write_text(
        "def read(ctx):\n"
        "    return float(ctx.stats['decode_tokens'])\n")
    bench["per_layer"].append({
        "name": "decode_rows.itl", "unit": "rows", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "itl_p95_ms", "workloads": ["tiny.chat"]})
    r = run_tiny(home, bench, seed=41, trace=True,
                 trace_s=1.5)
    assert r["correct"] is True, r["compared"]
    m = r["metrics"]
    assert m["decode_rows.itl"]["value"] > 0
    assert 0 < m["prefill_fill.ttft"]["value"] <= 100
    assert m["prefill_ms.ttft"]["value"] > 0
    assert m["decode_step_ms.itl"]["value"] > 0
    # the CPU has no kernel events: the roofline share stays silent
    assert "arrayflex_gemm_roofline.itl" not in m
    assert r["device"]["window_s"] > 0
    assert len(r["breakdown"]["idle_gaps"]) <= 10


def test_no_chip_means_no_result(capsys):
    import run
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run.main(["--workload", "qwen2-0.5b.short-burst", "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert out.getvalue() == ""


def _add_config(home, bench, name, reference, **sizes):
    """A tiny configuration ``name`` (the tiny one with ``sizes`` replaced
    and ``reference`` naming its architecture module) and a cell
    ``<name>.chat`` on the tiny chat mix that reports what tiny.chat does."""
    conf = json.loads((home / "configs" / "tiny.json").read_text())
    conf.update(sizes, name=name, reference=reference)
    conf["program"]["qkv_bias"] = False
    (home / "configs" / f"{name}.json").write_text(json.dumps(conf))
    bench["configs"].append({"name": name, "source": "test",
                             "file": f"configs/{name}.json", "reduced": [],
                             "why": "test"})
    bench["workloads"].append({"name": f"{name}.chat", "config": name,
                               "traffic": "tchat", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tiny.chat" in m.get("workloads", []):
            m["workloads"].append(f"{name}.chat")
    return f"{name}.chat"


def _run(home, bench, cell, seed):
    from chipbench import harness
    return harness.run_cell(bench, cell, seed, 4.0, False,
                            t_process=time.perf_counter(), root=home,
                            home=home, require_chip=False)


def test_a_new_architecture_is_a_module_and_an_entry(tiny_home):
    """The program's qwen2-0.5b config with no QKV bias and head_dim twice
    hidden / heads, served through the harness and compared with its own
    reference (its own leaves, no biases): correct, with no edit to the
    shared code."""
    home, bench = tiny_home
    mirror(home, NOBIAS)
    conf = json.loads((home / "configs" / "tiny.json").read_text())
    head_dim = 2 * conf["hidden_size"] // conf["num_attention_heads"]
    cell = _add_config(home, bench, "tiny-nobias", NOBIAS, head_dim=head_dim)
    r = _run(home, bench, cell, 3000000011)
    assert r["correct"] is True, r["compared"]
    assert r["compared"]["tokens_checked"]["value"] >= 200
    assert r["failed"] == 0


WRITE_HALF_HEAD = """
from pathlib import Path
from chipbench.harness import load_arch
_m = load_arch(Path(__file__).resolve().parents[2] / {nobias!r})
globals().update({{n: getattr(_m, n) for n in dir(_m)
                   if not n.startswith("__")}})


def dims(conf):
    return dict(_m.dims(conf), head_dim=_m.dims(conf)["head_dim"] // 2)
"""


@pytest.mark.parametrize("case", ["bias_the_program_lacks",
                                  "head_dim_the_program_lacks"])
def test_a_module_that_disagrees_with_the_program_is_refused(tiny_home,
                                                             case):
    """Qwen2's own module (biases) on a program without QKV bias, and a
    module whose ``dims`` halve the head_dim its program is given: the
    layout check refuses each before anything runs, naming the leaves."""
    home, bench = tiny_home
    mirror(home, NOBIAS)
    if case == "bias_the_program_lacks":
        cell = _add_config(home, bench, "tiny-bias",
                           "benchmarks/chip/archs/qwen2.py")
        want = "('blocks/0/attn/wq/b', '-', 'float32[2, 64]')"
    else:
        (home / "tests" / "bench_chip" / "half_head.py").write_text(
            WRITE_HALF_HEAD.format(nobias=NOBIAS))
        cell = _add_config(home, bench, "tiny-half",
                           "tests/bench_chip/half_head.py", head_dim=32)
        want = ("('blocks/0/attn/wq/w', 'float32[2, 64, 128]', "
                "'float32[2, 64, 64]')")
    with pytest.raises(RuntimeError, match="parameter layout differs") as e:
        _run(home, bench, cell, 5)
    assert want in str(e.value)
    assert str(REPO) not in str(e.value)
