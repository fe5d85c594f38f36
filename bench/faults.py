"""Faults planted under the timed path, to show that ``correct`` turns
false for each fault a serving cell can have.

Each fault takes ``patch(obj, name, value)`` (``setattr``, or pytest's
``monkeypatch.setattr``) and replaces the fused decode step that every
island batcher builds, before the stack is built. ``control.py --fault``
reads them at a cell's own size on the chip; ``tests/test_faults.py``
runs them through the whole harness on the CPU.
"""


def _wrap_decode(patch, change):
    """Replace the fused decode step the batchers build with ``change``
    applied to its outputs."""
    import repro.serving.batcher as batcher
    make = batcher.make_fused_decode_step

    def faulty(model, window=None):
        step = make(model, window)

        def run_step(params, cache, *args):
            return change(cache, step(params, cache, *args))
        return run_step
    patch(batcher, "make_fused_decode_step", faulty)


def altered_token(patch):
    """Each decoded token is replaced, where it is produced, by its
    neighbour in the vocabulary."""
    def change(cache, out):
        logits, last, gen, new_cache = out
        return logits, (last + 1) % 512, (gen + 1) % 512, new_cache
    _wrap_decode(patch, change)


def state_unchanged(patch):
    """The decode step hands back the page pool as it was given: the K and
    V of every decoded token are lost. The step writes each row's new
    token at (layer, its current page, position in the page) of every
    stacked (layers, pages, page_size, ...) pool leaf and nowhere else, so
    putting back what those places held returns the pool unchanged,
    without the copy of the whole pool that returning the donated input
    would take (more than the chip holds at a cell's size)."""
    import jax
    import jax.numpy as jnp

    import repro.serving.batcher as batcher
    make = batcher.make_fused_decode_step

    def faulty(model, window=None):
        step = make(model, window)

        def run_step(params, cache, last_tok, host_mask, host_tok, pos,
                     block_table, *rest):
            def where(leaf):
                assert leaf.ndim == 5, leaf.shape
                ps = leaf.shape[2]
                rows = jnp.arange(block_table.shape[0])
                return block_table[rows, pos // ps], pos % ps
            held = jax.tree.map(lambda x: x[:, where(x)[0], where(x)[1]],
                                cache)
            logits, last, gen, new = step(params, cache, last_tok, host_mask,
                                          host_tok, pos, block_table, *rest)
            new = jax.tree.map(
                lambda x, h: x.at[:, where(x)[0], where(x)[1]].set(h),
                new, held)
            return logits, last, gen, new
        return run_step
    patch(batcher, "make_fused_decode_step", faulty)


FAULTS = {"altered_token": altered_token, "state_unchanged": state_unchanged}
