"""The chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/chip/run.py --workload qwen2-0.5b.short-burst --seed 7 \
        --seconds 51 --trace 0

Runs on the machine it is started on, in this one process, which holds the
chip.  Exits non-zero with no result when JAX finds no TPU or fewer chips
than the cell asks for.  The last line of standard output is the result,
one JSON object; the numbers compared for ``correct`` end standard error.

``--control KEY=VALUE`` replaces one program setting of the configuration:
``gemm_backend=arrayflex_w8a8``, the program's own lower-precision path, is
the control that must come out not correct; ``--rate R`` overrides an open-loop mix's
session rate (the knee sweep).  Neither is used by a benchmark run.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from chipbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default=None)
    ap.add_argument("--rate", type=float, default=None)
    a = ap.parse_args(argv)
    bench = json.loads((harness.CHECKOUT / "BENCHMARK.json").read_text())
    try:
        res = harness.run_cell(bench, a.workload, a.seed, a.seconds,
                               bool(a.trace), t_process=T_PROCESS,
                               overrides=dict([a.control.split("=", 1)])
                               if a.control else None, rate=a.rate)
    except harness.NoChip as exc:
        print(f"no result: {exc}", file=sys.stderr)
        return 3
    for k, v in res["compared"].items():
        print(f"compared {k} = {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
