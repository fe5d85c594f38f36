"""Share of its roofline that the chunked prefill attention kernel
reached: the least time the chip needs for the prompt tokens computed
while the profiler ran (causal scores and values, each run's context read
once; ``counts.prefill_attn_work``, per computed prompt token of the
window's requests), over the summed device time of the kernel's calls."""
import counts


def read(ctx):
    t = ctx.trace["kernels"].get("chunked_prefill_attention")
    p = ctx.prefill_traced
    if not t or not p["attn_flops"] or not p["complete"]:
        return None
    return counts.roofline_share(p["attn_flops"], p["attn_bytes"], t,
                                 ctx.kind)
