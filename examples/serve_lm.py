"""Serve a small model with batched requests through the continuous-batching
engine (prefill + fused decode ticks), reporting ArrayFlex's decode-regime
plan for the same model.

Run:  PYTHONPATH=src python examples/serve_lm.py
"""

from repro.configs import ARCHS, SHAPES
from repro.core import planner
from repro.launch import serve


def main():
    # the decode-regime ArrayFlex plan (small-T: where the paper's
    # technique pays off for LLMs — see benchmarks/paper_figs.py)
    cfg_full = ARCHS["qwen2-0.5b"]
    plan = planner.plan_model(cfg_full, SHAPES["decode_32k"])
    print(f"ArrayFlex decode plan for {cfg_full.name}: "
          f"latency -{plan['latency_saving']*100:.1f}%, "
          f"EDP {plan['edp_gain']:.2f}x vs fixed-pipeline SA")
    ks = {}
    for p in plan["plans"]:
        ks.setdefault(p.k, []).append(p.gemm.name)
    for k, names in sorted(ks.items()):
        print(f"  k={k}: {len(names)} GEMM kinds e.g. {names[:3]}")

    reqs = serve.main(["--arch", "qwen2-0.5b", "--reduced", "--requests",
                       "6", "--max-new", "16"])
    assert all(len(r.out_tokens) == 16 for r in reqs)
    print("example complete")


if __name__ == "__main__":
    main()
