"""Host milliseconds of one orchestrator tick outside the island batchers:
routing (WAVES and the routing kernel), MIST, TIDE and LIGHTHOUSE
bookkeeping, completion. The wall time of each ``TickOrchestrator.tick``
in the window minus the batchers' ``tick`` calls inside it, both timed by
the benchmark around those public calls."""


def read(ctx):
    ms = ctx.orch_host_ms
    return sum(ms) / len(ms) if ms else None
