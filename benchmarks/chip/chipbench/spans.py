"""The arithmetic behind the per-layer metrics read from the serving
engine's own spans: the deltas, over the traced interval, of the counters
its ``engine.*`` spans feed (``repro.serving.engine``).  A reading returns
None where the interval holds no such event, and where the program keeps no
such counter (a program older than the spans), so it never raises there."""
from __future__ import annotations


def _ms_per(ctx, total: str, count: str, less=()):
    st = ctx.stats
    if any(k not in st for k in (total, count, *less)) or not st[count]:
        return None
    return 1e3 * (st[total] - sum(st[k] for k in less)) / st[count]


def first_token_ms(ctx):
    """Admission to first output token, per request that got one."""
    return _ms_per(ctx, "first_token_s", "first_tokens")


def sample_ms(ctx):
    """The sampler, from its eager programs' launch to the tokens on the
    host, per decode dispatch."""
    return _ms_per(ctx, "sample_time_s", "decode_dispatches")


def engine_host_ms(ctx):
    """Host time per engine tick outside the two waits on the model step
    (the prefill's block_until_ready and the decode's finiteness readback)."""
    return _ms_per(ctx, "step_time_s", "steps",
                   ("prefill_wait_s", "decode_wait_s"))
