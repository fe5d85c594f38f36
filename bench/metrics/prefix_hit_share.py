"""Share of the prompt tokens sent in the window that the scheduler served
from pages already in the pool: the batchers' ``prefix_tokens_skipped``
counter over the window, against the benchmark's own count of the prompt
tokens it sent."""


def read(ctx):
    w = ctx.window
    if not w.get("prompt_tokens_sent") or "prefix_skipped" not in w:
        return None
    return 100.0 * w["prefix_skipped"] / w["prompt_tokens_sent"]
