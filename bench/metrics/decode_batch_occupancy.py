"""Share of the decode slots that held a sequence in each fused decode
step of the window: the batchers' ``decode_tokens`` over
``decode_steps`` times the slots."""


def read(ctx):
    w = ctx.window
    if not w.get("decode_steps"):
        return None
    return 100.0 * w["decode_tokens"] / (w["decode_steps"] * ctx.slots)
