"""Device milliseconds of the fused prefill step programs per thousand
prompt tokens dispatched while the profiler ran."""


def read(ctx):
    secs = ctx.trace["modules"].get("fused_prefill_step")
    toks = ctx.prefill_traced["tokens"]
    if not secs or not toks:
        return None
    return 1000.0 * sum(secs) / (toks / 1000.0)
