"""Kernel microbenchmarks.

The kernels here are called with their default ``interpret=True``, so
they execute in interpret mode on any backend and the absolute times are
NOT TPU performance — these rows exist to (a) prove the kernels execute
and match their oracles at benchmark shapes and (b) time the portable XLA
paths that the CPU examples actually use."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.models.attention import attend_blocked, attend_naive


def _time(fn, *args, reps=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def run():
    lines = []
    key = jax.random.PRNGKey(0)
    # XLA blocked-flash vs naive (the production CPU/compile path)
    B, S, H, Hkv, D = 1, 2048, 8, 2, 64
    q = jax.random.normal(key, (B, S, H, D), jnp.float32)
    k = jax.random.normal(key, (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(key, (B, S, Hkv, D), jnp.float32)
    pos = jnp.arange(S)
    f_naive = jax.jit(lambda q, k, v: attend_naive(q, k, v, pos, pos,
                                                   D ** -0.5))
    f_blk = jax.jit(lambda q, k, v: attend_blocked(q, k, v, pos, pos,
                                                   D ** -0.5))
    lines.append(("xla_attn/naive_2k", _time(f_naive, q, k, v), "S=2048"))
    lines.append(("xla_attn/blocked_2k", _time(f_blk, q, k, v),
                  "triangular schedule"))
    # kernels (interpret mode 'works + matches' check at small shape)
    from repro.kernels.flash_attention import flash_attention
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)[:, :256]
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, S, D)[:, :256]
    us = _time(lambda a, b, c: flash_attention(a, b, c), qf, kf, kf, reps=2)
    import numpy as np
    o = flash_attention(qf, kf, kf)
    o_ref = ref.flash_attention(qf, kf, kf, D ** -0.5)
    err = float(jnp.max(jnp.abs(o - o_ref)))
    lines.append(("pallas_interp/flash_256", us, f"allclose_err={err:.1e}"))
    # chunked-prefill kernel vs the monolithic flash prefill: replay one
    # 128-token prompt through page-gathered chunks of each size (this is
    # the serving admission path); parity is against the same full causal
    # attention the monolithic kernel computes
    from repro.kernels.chunked_prefill import chunked_prefill_attention
    Bc, Hc, Hkvc, Dc, psc = 1, 8, 2, 64, 16
    Sc = 128
    Nc = Sc // psc
    qc = jax.random.normal(key, (Bc, Sc, Hc, Dc), jnp.float32)
    kc = jax.random.normal(jax.random.PRNGKey(5), (Bc, Sc, Hkvc, Dc))
    vc = jax.random.normal(jax.random.PRNGKey(6), (Bc, Sc, Hkvc, Dc))
    kpc = kc[0].reshape(Nc, psc, Hkvc, Dc)
    vpc = vc[0].reshape(Nc, psc, Hkvc, Dc)
    btc = jnp.arange(Nc)[None]
    qfc = qc.transpose(0, 2, 1, 3).reshape(Bc * Hc, Sc, Dc)
    kfc = kc.transpose(0, 2, 1, 3).reshape(Bc * Hkvc, Sc, Dc)
    vfc = vc.transpose(0, 2, 1, 3).reshape(Bc * Hkvc, Sc, Dc)
    us = _time(lambda a, b, c: flash_attention(a, b, c, block_q=64,
                                               block_k=64),
               qfc, kfc, vfc, reps=2)
    o_mono = flash_attention(qfc, kfc, vfc, block_q=64, block_k=64)
    o_mono = o_mono.reshape(Bc, Hc, Sc, Dc).transpose(0, 2, 1, 3)
    lines.append(("pallas_interp/prefill_monolithic_128", us,
                  "one dispatch"))
    for T in (16, 32, 64):
        def replay(q=qc, T=T):
            outs = [chunked_prefill_attention(
                q[:, s:s + T], kpc, vpc, btc[:, :(s + T) // psc],
                jnp.array([s], jnp.int32)) for s in range(0, Sc, T)]
            return jnp.concatenate(outs, axis=1)
        us = _time(replay, reps=2)
        err = float(jnp.max(jnp.abs(replay() - o_mono)))
        lines.append((f"pallas_interp/prefill_chunked_T{T}", us,
                      f"{Sc // T} dispatches allclose_err={err:.1e}"))
    # SSD XLA vs kernel path
    from repro.kernels.ssd import ssd_full
    from repro.models.ssm import ssd_chunked
    Bs, Ss, Hs, P, N, Q = 1, 512, 4, 32, 32, 64
    x = jax.random.normal(key, (Bs, Ss, Hs, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(key, (Bs, Ss, Hs)))
    a = -jnp.exp(jax.random.normal(key, (Hs,)) * 0.3)
    B_ = jax.random.normal(key, (Bs, Ss, N))
    C_ = jax.random.normal(key, (Bs, Ss, N))
    f_xla = jax.jit(lambda *t: ssd_chunked(*t, Q)[0])
    lines.append(("xla_ssd/chunked_512", _time(f_xla, x, dt, a, B_, C_),
                  f"Q={Q}"))
    err = float(jnp.max(jnp.abs(ssd_full(x, dt, a, B_, C_, Q)
                                - ref.ssd_full(x, dt, a, B_, C_, Q))))
    lines.append(("pallas_interp/ssd_512", 0.0, f"allclose_err={err:.1e}"))
    return lines


if __name__ == "__main__":
    for row in run():
        print(row)
