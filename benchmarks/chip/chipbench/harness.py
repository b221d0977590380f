"""Run one benchmark cell: set-up, lead-in, the measured window, the drain,
the per-layer readings of a traced run, and the comparison with the plain
reference that decides ``correct``.

A cell is found by its name in ``BENCHMARK.json``; its configuration is
``<file>`` of the named configuration, its traffic ``traffic/<mix>.json``
and each per-layer metric ``metrics/<metric>.py``, all looked up by name, so
a new cell, mix or metric is a new file and an entry, never an edit here.
The configuration's ``reference`` key names its architecture module (a
path from the checkout, e.g. ``benchmarks/chip/archs/qwen2.py``), which
holds everything that knows what a layer is: the names in ``ARCH_NAMES``.
So a new architecture is a new module too.

The harness drives the program only through ``repro.configs``,
``repro.models.lm`` (the parameter layout) and ``ServingEngine.submit`` /
``ServingEngine.step``; what the window produced (tokens, engine counters)
is read from the benchmark side.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np

from chipbench import reference, roofline, traffic, weights, xtrace

BENCH_DIR = Path(__file__).resolve().parents[1]
CHECKOUT = BENCH_DIR.parents[1]
# The ArrayFlex kernel's device events are custom calls named after the
# Pallas kernel's builder, ``_gemm.N`` and ``_expert_gemm.N`` (the batched
# qk/pv calls), as read by hand from a TPU v5e trace (PERF.md).
KERNEL_EVENT = r"^_(expert_)?gemm\.\d+ custom-call "
TRACE_S = 4.0
# what an architecture module exports: its sizes from the configuration file
# (``dims``), the program's ModelConfig fields they state
# (``program_kwargs``), the reference's arguments (``reference_kwargs``),
# one layer's leaves (``LAYER_LEAVES``) and plain float32 layer (``layer``),
# and the counts behind the per-layer shares (the rest)
ARCH_NAMES = ("dims", "program_kwargs", "reference_kwargs", "LAYER_LEAVES",
              "layer", "kernel_calls_per_dispatch", "step_least_time",
              "model_flops")


# (start, end, function) of every compilation, on the wall clock
COMPILES = []


def _on_compile(event, start, end, **kw):
    if event.endswith("backend_compile_duration"):
        COMPILES.append((start, end, kw.get("fun_name", "")))


jax.monitoring.register_event_time_span_listener(_on_compile)


class NoChip(RuntimeError):
    """JAX found no accelerator of the kind and count the cell needs."""


def log(*a):
    print(*a, file=sys.stderr, flush=True)


# ------------------------------------------------------------------ lookup
def load_cell(bench: dict, workload: str, root: Path = BENCH_DIR,
              home: Path = CHECKOUT) -> SimpleNamespace:
    """The cell's entry, configuration, mix and metrics.  ``home`` holds
    ``BENCHMARK.json`` (configuration files are relative to it); ``root``
    holds ``traffic/``, ``metrics/`` and the peak table."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    conf_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    conf = json.loads((home / conf_entry["file"]).read_text())
    mix = json.loads((root / "traffic" / f"{cell['traffic']}.json")
                     .read_text())

    def here(m):
        return workload in m.get("workloads", [workload])

    e2e = [m for m in bench["end_to_end"] if here(m)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if here(m) and m["moves"] in reported]
    return SimpleNamespace(name=workload, cell=cell, conf=conf, mix=mix,
                           e2e=e2e, per_layer=per_layer, root=root,
                           arch=load_arch(home / conf["reference"]))


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(root: Path, name: str):
    return _load_module(root / "metrics" / f"{name}.py",
                        f"chipbench_metric_{name}").read


def load_arch(path: Path):
    """The architecture module at ``path``; it must export ``ARCH_NAMES``."""
    mod = _load_module(path, f"chipbench_arch_{Path(path).stem}")
    missing = [n for n in ARCH_NAMES if not hasattr(mod, n)]
    if missing:
        raise AttributeError(f"architecture module {path} lacks {missing}")
    return mod


def model_config(conf: dict, arch, overrides: dict | None = None):
    """The program's ModelConfig: its own published config, replaced by
    every size of the configuration file that the architecture module maps
    (and by ``overrides``, which a control uses to switch the program to a
    lower precision).  A dict given for a field that is itself a config
    (an expert group, say) replaces those fields of the program's own."""
    from repro.configs import get_config
    prog = conf["program"]
    base = get_config(prog["config"])
    kw = arch.program_kwargs(conf)
    kw.update(param_dtype=prog["param_dtype"],
              compute_dtype=prog["compute_dtype"],
              gemm_backend=prog["gemm_backend"])
    kw.update(overrides or {})
    kw = {k: dataclasses.replace(getattr(base, k), **v)
          if isinstance(v, dict) else v for k, v in kw.items()}
    cfg = dataclasses.replace(base, **kw)
    for k, v in kw.items():
        if getattr(cfg, k) != v:
            raise ValueError(f"{k}: program holds {getattr(cfg, k)!r}, "
                             f"configuration file says {v!r}")
    return cfg


# ------------------------------------------------------------------ stats
def nearest_rank(values, q: float) -> float:
    """The q-quantile by nearest rank (inf counts as a missing sample)."""
    if not values:
        return math.inf
    v = sorted(values)
    return v[max(0, math.ceil(q * len(v)) - 1)]


class Tracker:
    """Host-clock stamps of each request's output tokens, and (in a traced
    interval) the live rows of each dispatch."""

    def __init__(self, engine, origin: float, clock):
        self.engine, self.origin, self.clock = engine, origin, clock
        self.live = {}            # rid -> (item, req)
        self.stamps = {}          # rid -> [t, ...] seconds from origin
        self.record = False
        self.prefill_dispatches = []   # rows per prefill dispatch
        self.decode_dispatches = []    # contexts per decode dispatch
        self.steps = []                # (begin, end, tokens) in the window

    def submit(self, item, req):
        self.engine.submit(req)
        self.live[item.rid] = (item, req)
        self.stamps[item.rid] = []

    def step(self):
        """One engine step; returns its end (seconds from the origin) and
        the output tokens it produced."""
        eng = self.engine
        if self.record:
            before = {id(s): (s.state, s.prefill_done) for s in eng.active}
            st0 = dict(eng.stats)
        eng.step()
        now = self.clock() - self.origin
        ctx = []
        for rid, (item, req) in list(self.live.items()):
            ts = self.stamps[rid]
            for i in range(len(ts), len(req.out_tokens)):
                ts.append(now)
                ctx.append(len(item.prompt) + i)
            if req.done:
                del self.live[rid]
        if self.record:
            rows = []
            for s in eng.active:
                st, pd0 = before.get(id(s), ("prefill", s.n_shared
                                             * eng.page_size))
                if st == "prefill" and s.prefill_done > pd0:
                    rows.append((pd0, s.prefill_done - pd0))
            if eng.stats["prefill_dispatches"] > st0["prefill_dispatches"]:
                self.prefill_dispatches.append(rows)
            if eng.stats["decode_dispatches"] > st0["decode_dispatches"]:
                self.decode_dispatches.append(ctx)
        return now, len(ctx)


# ------------------------------------------------------------------ run
def check_devices(chips: int, peaks_path: Path):
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(f"need {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return roofline.peaks(devs[0].device_kind, peaks_path)


def run_cell(bench: dict, workload: str, seed: int, seconds: float,
             trace: bool, *, t_process: float, root: Path = BENCH_DIR,
             home: Path = CHECKOUT, require_chip: bool = True,
             overrides: dict | None = None, rate: float | None = None,
             fault=None, trace_s: float = TRACE_S) -> dict:
    """One run of one cell; returns the result line as a dict.

    ``overrides`` replaces program settings of the configuration (the
    lower-precision control);
    ``rate`` overrides an open-loop mix's rate (the knee sweep); ``fault``
    is called with the built engine to break it (tests of the check);
    ``require_chip=False`` (tests on the CPU) skips the look for a chip and
    takes the peaks of a TPU v5e."""
    clock = time.perf_counter
    c = load_cell(bench, workload, root, home)
    peaks_path = root / "peaks.json"
    peak = (check_devices(c.cell["chips"], peaks_path) if require_chip
            else roofline.peaks("TPU v5 lite", peaks_path))

    sys.path.insert(0, str(CHECKOUT / "src"))
    from repro.launch.compile_cache import setup_compile_cache
    from repro.models import lm
    from repro.serving import ServeConfig, ServingEngine
    from repro.serving.engine import Request
    setup_compile_cache()

    mix = dict(c.mix)
    if rate is not None:
        mix["rate"] = rate
    conf, e, arch = c.conf, mix["engine"], c.arch
    cfg = model_config(conf, arch, overrides)
    dims = arch.dims(conf)
    expected = jax.eval_shape(lambda: lm.init_params(cfg, jax.random.PRNGKey(0)))
    log(f"[{workload}] set-up: JAX and program imported at "
        f"{clock() - t_process:.1f} s")
    params = weights.program_params(expected, dims, seed, cfg.padded_vocab,
                                    cfg.tie_embeddings, arch.LAYER_LEAVES)
    jax.block_until_ready(params)
    log(f"[{workload}] set-up: weights made at {clock() - t_process:.1f} s")
    sc = ServeConfig(max_batch=e["max_batch"], max_seq=e["max_seq"],
                     eos_id=-1, seed=seed % 2**31, prefill_mode="batched",
                     prefill_chunk=e["prefill_chunk"],
                     kv_pages=e["kv_pages"], page_size=e["page_size"],
                     prefix_cache=e["prefix_cache"])
    # the engine holds the only reference: a quantizing control frees the
    # float32 tree once its own copy is made
    engine = ServingEngine(cfg, params, sc)
    del params
    if fault is not None:
        fault(engine)
    # warm-up: one request through one prefill and two decode dispatches
    warm = Request(prompt=[1, 2, 3], max_new_tokens=2, rid=-1)
    engine.submit(warm)
    while not warm.done:
        engine.step()
    log(f"[{workload}] set-up: engine built and warmed up at "
        f"{clock() - t_process:.1f} s")
    items = traffic.generate(mix, seed, seconds, dims["vocab"], e["max_seq"])
    log(f"[{workload}] schedule: {len(items)} requests, "
        f"{sum(it.due >= 0 for it in items)} due in the window")

    lead = float(mix["lead_in_s"])
    origin = clock() + lead
    wall0 = time.time() - clock() + origin    # the origin on the wall clock
    trk = Tracker(engine, origin, clock)
    window = _drive(trk, items, engine, Request, seconds, trace, clock,
                    origin, trace_s)
    setup_s = origin - t_process
    due = [it for it in items if 0 <= it.due < seconds]
    open_loop = mix["kind"] == "sessions"
    if open_loop:
        _drain(trk, due, float(mix["drain_s"]), clock, origin, seconds)

    # ---- end-to-end metrics
    metrics, failed, attempted = {}, 0, 0
    if open_loop:
        attempted = len(due)
        ttft, itl = [], []
        drained = seconds + float(mix["drain_s"])
        for it in due:
            req = trk.reqs.get(it.rid)
            ts = trk.stamps.get(it.rid, [])
            if req is None or req.outcome != "ok" or not ts:
                # missing: its first token came later than the drain's end
                failed += 1
                ttft.append(drained - it.due)
                continue
            ttft.append(ts[0] - it.due)
            itl += list(np.diff(ts))
        metrics["ttft_p90_ms"] = 1e3 * nearest_rank(ttft, 0.90)
        metrics["itl_p95_ms"] = 1e3 * nearest_rank(itl, 0.95)
    else:
        # every step begun in the window, from the first one's start to
        # the last one's end: all the work and all the time between them
        steps = [st for st in trk.steps if 0 <= st[0] < seconds]
        span = steps[-1][1] - steps[0][0] if steps else 0.0
        metrics["output_tokens_per_s"] = (
            sum(st[2] for st in steps) / span if span > 0 else 0.0)
        for rid, ts in trk.stamps.items():
            req = trk.reqs[rid]
            if ts or req.done:
                attempted += 1
                failed += int(req.done and req.outcome != "ok")
    metrics["setup_s"] = setup_s
    lag = [trk.submitted[it.rid] - it.due for it in items
           if it.rid in trk.submitted and it.due >= 0]
    longest = max(trk.steps, key=lambda st: st[1] - st[0],
                  default=(0.0, 0.0, 0))
    inside = [c for c in COMPILES
              if c[1] >= wall0 and c[0] <= wall0 + window["elapsed"]]
    log(f"[{workload}] window steps {len(trk.steps)}, longest "
        f"{1e3 * (longest[1] - longest[0]):.1f} ms at {longest[0]:.3f} s; "
        f"compilations inside the window {len(inside)} "
        f"{[c[2] for c in inside][:8]}")
    log(f"[{workload}] window {window['elapsed']:.3f} s, due {len(due)}, "
        f"attempted {attempted}, failed {failed}, backlog at start "
        f"{window['backlog_start']} at end {window['backlog_end']}, "
        f"submit lag max {1e3 * max(lag, default=0):.1f} ms, "
        f"engine stats {json.dumps(engine.stats)}")

    # ---- device and memory (read before the program's state is freed)
    devs = jax.devices()
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devs)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(mem)}
    result = {"correct": False, "attempted": attempted, "failed": failed}
    per_layer = None
    if trace:
        red = xtrace.reduce(window["trace"], KERNEL_EVENT)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        ctx = SimpleNamespace(
            stats=window["trace_stats"], dims=dims, engine=e, peak=peak,
            arch=arch, trace=red, prefill_dispatches=trk.prefill_dispatches,
            decode_dispatches=trk.decode_dispatches)
        per_layer = {}
        for m in c.per_layer:
            v = load_reader(c.root, m["name"])(ctx)
            if v is not None:
                per_layer[m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
        log(f"[{workload}] trace: {json.dumps(red)[:4000]}")

    # ---- correctness: every request served tokens, with the tokens it was
    # served (an offline window closes long before most of its requests
    # finish); free the program, then compare
    served = [(it, list(trk.reqs[it.rid].out_tokens)) for it in items
              if it.rid in trk.reqs and trk.reqs[it.rid].out_tokens
              and trk.reqs[it.rid].outcome in (None, "ok")]
    expect_short = sum(len(o) != it.max_new for it, o in served
                       if trk.reqs[it.rid].outcome == "ok")
    del engine, trk
    gc.collect()
    t_ref = clock()
    per_req = reference.served_gaps(
        arch, dims, seed, [(it.prompt, o) for it, o in served],
        padded_vocab=cfg.padded_vocab, **arch.reference_kwargs(conf))
    gaps = np.concatenate(per_req) if per_req else np.zeros(0)
    lim = conf["correct"]
    known = gaps[np.isfinite(gaps)]
    compared = {
        "logit_gap_mean": {"value": float(known.mean()) if len(known)
                           else 0.0, "limit": lim["logit_gap_mean"]},
        "tokens_checked": {"value": int(len(gaps)),
                           "limit": lim["tokens_checked_min"]},
        "tokens_outside_vocab": {"value": int(len(gaps) - len(known)),
                                 "limit": 0},
        "short_outputs": {"value": int(expect_short), "limit": 0},
    }
    result["correct"] = bool(
        compared["logit_gap_mean"]["value"] <= lim["logit_gap_mean"]
        and len(gaps) >= lim["tokens_checked_min"]
        and len(known) == len(gaps) and expect_short == 0)
    worst = max((float(g.mean()) for g in per_req if len(g)), default=0.0)
    log(f"[{workload}] reference: {len(served)} requests, {len(gaps)} "
        f"tokens, {clock() - t_ref:.1f} s; gap max "
        f"{float(np.max(known)) if len(known) else math.nan:.6g}, mean "
        f"{float(np.mean(known)) if len(known) else math.nan:.6g}, "
        f"p99 {float(np.quantile(known, 0.99)) if len(known) else 0:.6g}, "
        f"worst request's mean {worst:.6g}, "
        f"tokens off the reference's best {int(np.sum(known > 0))}")

    if trace:
        result["metrics"] = per_layer
    else:
        units = {m["name"]: m["unit"] for m in c.e2e}
        result["metrics"] = {k: {"value": v, "unit": units[k]}
                             for k, v in metrics.items() if k in units}
    result["device"] = device
    result["compared"] = compared
    for k, v in compared.items():
        log(f"compared {k} = {v['value']} (limit {v['limit']})")
    return result


def _drive(trk, items, engine, Request, seconds, trace, clock, origin,
           trace_s):
    """Lead-in and window: submit each request at its due time, step the
    engine while anything is queued or resident, sleep otherwise."""
    ann = jax.profiler.TraceAnnotation
    trk.reqs, trk.submitted = {}, {}
    pending = list(reversed(items))
    t_on = seconds / 2 - trace_s / 2
    state = {"backlog_start": None, "trace_stats": None, "trace": None}
    tdir = span = None

    def backlog():
        return sum(1 for it, r in trk.live.values() if it.due >= 0) + sum(
            1 for it in pending if it.due < now)

    while True:
        now = clock() - origin
        if state["backlog_start"] is None and now >= 0:
            state["backlog_start"] = backlog()
        if now >= seconds:
            break
        if trace and tdir is None and now >= t_on:
            tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
            jax.profiler.start_trace(tdir)
            span = ann("bench.traced")
            span.__enter__()
            trk.record = True
            st0 = dict(engine.stats)
        elif trk.record and now >= t_on + trace_s:
            _stop_trace(trk, span, engine, st0, state, tdir)
        with ann("bench.submit"):
            while pending and pending[-1].due <= now:
                it = pending.pop()
                req = Request(prompt=it.prompt, max_new_tokens=it.max_new,
                              rid=it.rid)
                trk.reqs[it.rid] = req
                trk.submitted[it.rid] = clock() - origin
                trk.submit(it, req)
        if engine.queue or engine.active:
            with ann("bench.step"):
                end, n = trk.step()
            if now >= 0:
                trk.steps.append((now, end, n))
        else:
            nxt = pending[-1].due if pending else seconds
            with ann("bench.sleep"):
                time.sleep(max(0.0, min(nxt, seconds) - now))
    if trk.record:
        _stop_trace(trk, span, engine, st0, state, tdir)
    state["elapsed"] = now
    state["backlog_end"] = backlog()
    # requests due before the close but not yet offered go in now
    while pending and pending[-1].due < seconds:
        it = pending.pop()
        req = Request(prompt=it.prompt, max_new_tokens=it.max_new, rid=it.rid)
        trk.reqs[it.rid] = req
        trk.submitted[it.rid] = clock() - origin
        trk.submit(it, req)
    return state


def _stop_trace(trk, span, engine, st0, state, tdir):
    import shutil
    trk.record = False
    span.__exit__(None, None, None)
    jax.profiler.stop_trace()
    state["trace_stats"] = {k: engine.stats[k] - st0[k] for k in st0}
    state["trace"] = xtrace.load(xtrace.find_xplane(tdir))
    shutil.rmtree(tdir, ignore_errors=True)


def _drain(trk, due, limit_s, clock, origin, seconds):
    """Step on after the close until every request due in the window has
    finished, or ``limit_s`` has passed; what is unfinished then fails."""
    want = {it.rid for it in due}
    while clock() - origin < seconds + limit_s:
        if not any(rid in want for rid in trk.live):
            return
        trk.step()

