"""Pallas TPU kernels (+ jit wrappers in ops.py, oracles in ref.py).

The model layers pick an implementation by platform: on a TPU backend
``enabled()`` is true and the served attention sites (paged decode,
chunked prefill, flash prefill) call the native Pallas kernels; on every
other backend they run the pure-jnp oracles in ``ref.py`` and the XLA
paths in ``repro.models``. Tests that want the Pallas path on the CPU
monkeypatch ``enabled`` — ``ops`` then runs the kernels in interpret mode.
"""
import jax


def enabled() -> bool:
    """True where the Pallas kernels compile natively (a TPU backend)."""
    return jax.default_backend() == "tpu"
