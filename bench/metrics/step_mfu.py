"""The whole serving step's share of the chip's peak while the profiler
ran: operations the model needs for every token processed (prompt tokens
computed and tokens decoded, with attention over each one's context and
the output head for each decoded token), over the traced window's length
times the peak."""
import counts


def read(ctx):
    d, p = ctx.decode_traced, ctx.prefill_traced
    if not ctx.trace["window_s"] or not d["tokens"]:
        return None
    fl = counts.model_flops(ctx.cfg, d["tokens"], d["ctx_sum"], d["tokens"])
    fl += counts.model_flops(ctx.cfg, p["tokens"], p["ctx_sum"], p["heads"])
    return 100.0 * fl / (ctx.trace["window_s"]
                         * counts.peaks(ctx.kind)["flops_bf16"])
