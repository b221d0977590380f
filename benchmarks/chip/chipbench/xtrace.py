"""Reduce a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports: device busy time over the traced window, the kernel's events,
the device operations that took most time, and the longest idle gaps named
by what the host was doing in them.

``load`` reads the trace into plain lists; ``reduce`` works on those lists
alone, so a recorded trace checks it without a chip.  Host spans are the
benchmark's own ``jax.profiler.TraceAnnotation`` names (``bench.*``); the
window is the ``bench.traced`` span.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.traced"
CONTAINERS = ("while", "conditional", "call")


def op_name(text: str) -> str:
    """``name opcode type`` of an op event, whose name is its HLO text
    (``%name = type opcode(operands), ...``); the type loses its layout and
    a tuple type reads ``(...)``: e.g. ``_gemm.25 custom-call bf16[8192,896]``."""
    name, _, rest = text.partition(" = ")
    name = name.lstrip("%")
    if not rest:
        return name[:120]
    if rest.startswith("("):                 # tuple type: skip to its close
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        ty, rest = "(...)", rest[i + 1:]
    else:
        ty, _, rest = rest.partition(" ")
        ty = ty.split("{")[0]
    opcode = rest.strip().split("(")[0]
    return f"{name} {opcode} {ty}"


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                         "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def load(path: str) -> dict:
    """{"devices": {ordinal: [(name, start_ns, dur_ns), ...]},
    "host": [(name, start_ns, dur_ns), ...]} from one xplane file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs += [(op_name(e.name), float(e.start_ns),
                             float(e.duration_ns)) for e in line.events]
            devices[int(m.group(1))] = evs
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host += [(e.name, float(e.start_ns), float(e.duration_ns))
                         for e in line.events if e.name.startswith("bench.")]
    return {"devices": devices, "host": host}


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(tr: dict, kernel: str, top: int = 10) -> dict:
    """Window, busy and kernel time per device (averaged over devices), the
    top device ops by total time (loops and calls, which hold other ops,
    left out of the ranking but not out of busy time), and the longest idle
    gaps on device 0 named by the innermost host span over each gap's
    middle."""
    win = [(s, s + d) for n, s, d in tr["host"] if n == WINDOW_SPAN]
    if not win:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    w0, w1 = win[0]
    pat = re.compile(kernel)
    busy, kern_s, kern_n, by_name = [], [], [], defaultdict(float)
    merged0 = []
    for ordinal in sorted(tr["devices"]):
        ivs, ks, kn = [], 0.0, 0
        for name, s, d in tr["devices"][ordinal]:
            s2, e2 = max(s, w0), min(s + d, w1)
            if e2 <= s2:
                continue
            ivs.append((s2, e2))
            if name.split(" ")[1:2] not in ([c] for c in CONTAINERS):
                by_name[name] += (e2 - s2) * 1e-9
            if pat.search(name):
                ks += (e2 - s2) * 1e-9
                kn += 1
        merged = _union(ivs)
        if not merged0:
            merged0 = merged
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        kern_s.append(ks)
        kern_n.append(kn)
    n_dev = max(len(busy), 1)
    spans = sorted(((s, s + d, n) for n, s, d in tr["host"]
                    if n != WINDOW_SPAN), key=lambda x: x[0])
    gaps = []
    edges = [w0] + [x for iv in merged0 for x in iv] + [w1]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            mid = (a + b) / 2
            inner = [sp for sp in spans if sp[0] <= mid <= sp[1]]
            name = (min(inner, key=lambda sp: sp[1] - sp[0])[2] if inner
                    else "untraced")
            gaps.append([name, (b - a) * 1e-9])
    gaps.sort(key=lambda g: -g[1])
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": (w1 - w0) * 1e-9,
            "busy_s": sum(busy) / n_dev,
            "kernel_s": sum(kern_s) / n_dev,
            "kernel_calls": sum(kern_n) / n_dev,
            "device_ops": [[n, s / n_dev] for n, s in ops],
            "idle_gaps": gaps[:top]}
