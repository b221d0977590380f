"""The arithmetic behind the per-layer metrics.  Each metric file under
``metrics/`` names one of these as its ``read``.  A reading takes the traced
interval's context (engine counter deltas, the live rows of each dispatch,
the reduced device trace, the peaks) and returns a number, or None where the
interval holds nothing to read.  A share of a roofline or a peak is never
returned as 0 for lack of data."""
from __future__ import annotations

from chipbench import roofline


def _ratio(num, den, scale=100.0):
    return scale * num / den if den else None


def prefill_fill(ctx):
    e = ctx.engine
    return _ratio(ctx.stats["prefill_tokens"], ctx.stats["prefill_dispatches"]
                  * e["max_batch"] * e["prefill_chunk"])


def decode_fill(ctx):
    return _ratio(ctx.stats["decode_tokens"],
                  ctx.stats["decode_dispatches"] * ctx.engine["max_batch"])


def prefill_ms(ctx):
    return _ratio(ctx.stats["prefill_time_s"],
                  ctx.stats["prefill_dispatches"], 1e3)


def decode_step_ms(ctx):
    return _ratio(ctx.stats["decode_time_s"], ctx.stats["decode_dispatches"],
                  1e3)


def expected_kernel_calls(ctx):
    per_step = len(roofline.SITES) - 1
    return (per_step * ctx.dims["layers"] + 1) * (
        len(ctx.prefill_dispatches) + len(ctx.decode_dispatches))


def gemm_roofline(ctx):
    """Summed least time of every ArrayFlex kernel call in the interval over
    those calls' summed device time.  None unless the trace holds exactly
    the calls the dispatches imply."""
    red = ctx.trace
    if not red["kernel_calls"] or red["kernel_calls"] != \
            expected_kernel_calls(ctx):
        return None
    B, C = ctx.engine["max_batch"], ctx.engine["prefill_chunk"]
    least = 0.0
    for rows in ctx.prefill_dispatches:
        least += roofline.step_least_time(
            ctx.dims, ctx.peak, B * C, B, [(C, p + n) for p, n in rows])
    for live in ctx.decode_dispatches:
        least += roofline.step_least_time(
            ctx.dims, ctx.peak, B, B, [(1, c) for c in live])
    return _ratio(least, red["kernel_s"])


def step_mfu(ctx):
    """Model FLOPs of the served tokens over the traced window at the
    chip's bf16 peak."""
    if not (ctx.prefill_dispatches or ctx.decode_dispatches):
        return None
    flops = roofline.model_flops(
        ctx.dims, [r for rows in ctx.prefill_dispatches for r in rows],
        [c for live in ctx.decode_dispatches for c in live])
    return _ratio(flops, ctx.trace["window_s"]
                  * ctx.peak["bf16_flops_per_s"])


def device_idle_share(ctx):
    w = ctx.trace["window_s"]
    return 100.0 * (1.0 - ctx.trace["busy_s"] / w) if w > 0 else None
