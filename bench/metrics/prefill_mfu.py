"""The prefill step's share of the chip's peak: operations the model needs
for the prompt tokens computed while the profiler ran (projections, MLP,
causal attention, and the output head for the token per request that
yields its first answer token), over the fused prefill programs' device
time times the peak."""
import counts


def read(ctx):
    secs = ctx.trace["modules"].get("fused_prefill_step")
    p = ctx.prefill_traced
    if not secs or not p["tokens"] or not p["complete"]:
        return None
    fl = counts.model_flops(ctx.cfg, p["tokens"], p["ctx_sum"], p["heads"])
    return 100.0 * fl / (sum(secs) * counts.peaks(ctx.kind)["flops_bf16"])
