"""CPU runs of the chip benchmark's harness on a tiny cell: a sound run is
correct and reports its metrics, a traced run reads its per-layer metrics,
a configuration, mix and metric added as new files are found without an
edit, and a host with no chip gets no result."""
import io
import json
from contextlib import redirect_stdout

from conftest import run_tiny


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
