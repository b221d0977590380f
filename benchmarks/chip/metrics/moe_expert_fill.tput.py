"""Expert-kernel rows that carried a routed token: (token, held expert) pairs of live tokens over the rows the expert kernels computed, padding included, engine counters over the traced interval, in %.  None where the program has no such counters or computed no expert rows."""


def read(ctx):
    rows = ctx.stats.get("moe_expert_rows", 0)
    if not rows or "moe_assignments" not in ctx.stats:
        return None
    return 100.0 * ctx.stats["moe_assignments"] / rows
