"""Share of its roofline that the paged decode attention kernel reached:
the least time the chip needs for the tokens decoded while the
profiler ran, each reading its sequence's cached K and V once per layer
(``counts.decode_attn_work``), over the summed device time of the
kernel's calls."""
import counts


def read(ctx):
    t = ctx.trace["kernels"].get("paged_decode_attention")
    d = ctx.decode_traced
    if not t or not d["tokens"]:
        return None
    fl, by = counts.decode_attn_work(ctx.cfg, d["ctx_sum"], d["tokens"])
    return counts.roofline_share(fl, by, t, ctx.kind)
