"""The yardstick's shared roofline arithmetic: the (operations, bytes) of
one GEMM call with bf16 operands, and the table of peaks keyed by
``device_kind``.  What a step of one architecture calls, and how often, is
that architecture's module's business (``archs/<name>.py``).
"""
from __future__ import annotations

import json
from pathlib import Path

BF16 = 2
F32 = 4


def peaks(kind: str, path: Path) -> dict:
    table = json.loads(Path(path).read_text())
    if kind not in table:
        raise KeyError(f"device_kind {kind!r} is not in the peak table "
                       f"({sorted(table)})")
    return table[kind]


def gemm(m, k, n, *, w2=False, resid=False, out=BF16):
    """(flops, bytes) of one (m,k)@(k,n) call with bf16 operands."""
    nw = 2 if w2 else 1
    flops = 2.0 * m * k * n * nw
    by = BF16 * (m * k + nw * k * n) + out * m * n + (BF16 * m * n
                                                     if resid else 0)
    return flops, by
