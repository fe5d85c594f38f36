"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.chunked_prefill import chunked_prefill_attention
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_decode_attention
from repro.kernels.rglru import rglru_scan
from repro.kernels.ssd import ssd_chunk, ssd_full

KEY = jax.random.PRNGKey(0)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("BH,BHkv,Sq,Sk,D,bq,bk", [
    (4, 2, 256, 256, 64, 128, 128),
    (2, 1, 128, 128, 32, 64, 64),
    (8, 8, 256, 256, 128, 128, 128),
    (2, 2, 512, 512, 64, 128, 256),
])
def test_flash_attention_sweep(BH, BHkv, Sq, Sk, D, bq, bk, dtype):
    q = jax.random.normal(KEY, (BH, Sq, D), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (BHkv, Sk, D), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (BHkv, Sk, D), dtype)
    o = flash_attention(q, k, v, block_q=bq, block_k=bk)
    o_ref = ref.flash_attention(q, k, v, D ** -0.5)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,Hkv,S,D,valid", [
    (2, 8, 2, 1024, 64, 700),
    (1, 4, 1, 512, 128, 512),
    (3, 6, 6, 256, 32, 1),
    (2, 16, 4, 2048, 64, 1234),
])
def test_decode_attention_sweep(B, H, Hkv, S, D, valid, dtype):
    q = jax.random.normal(KEY, (B, H, D), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, Hkv, D), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, Hkv, D), dtype)
    o = decode_attention(q, k, v, valid, block_k=256)
    o_ref = ref.decode_attention(q, k, v, valid, D ** -0.5)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,Hkv,D,P,ps,N,valids", [
    (2, 8, 2, 64, 24, 16, 8, (1, 128)),      # near-empty + full
    (1, 4, 1, 64, 9, 8, 4, (17,)),           # mid-page ragged fill
    (3, 6, 6, 64, 12, 16, 3, (5, 31, 48)),   # per-sequence ragged levels
    (2, 16, 4, 64, 40, 32, 6, (100, 192)),   # larger pages
    (2, 32, 8, 128, 12, 16, 5, (7, 80)),     # qwen3-4b heads: G 4, D 128
    (2, 9, 3, 64, 12, 16, 5, (33, 64)),      # smollm-135m heads: G 3, D 64
    # empty rows beside full ones: the blocked kernel (D a multiple of
    # 128: qwen3-4b, glm4-9b's two KV heads, paligemma's D 256) and the
    # per-page kernel (D 64 and 32)
    (4, 32, 8, 128, 12, 16, 4, (0, 40, 0, 64)),
    (3, 9, 3, 64, 24, 16, 20, (320, 0, 17)),
    (3, 4, 2, 32, 24, 16, 20, (0, 300, 17)),
    (2, 32, 2, 128, 24, 16, 20, (0, 300)),
    (2, 8, 1, 256, 24, 16, 20, (300, 0)),
    # 40 pages: two full blocks and a partial third; fills that end
    # inside a page of a full block, of the partial block, at its end
    (3, 32, 8, 128, 48, 16, 40, (300, 617, 640)),
    (2, 9, 3, 64, 48, 16, 40, (617, 255)),
    # a stacked (L, P) pool read at its last layer
    (2, 32, 8, 128, (3, 24), 16, 20, (200, 5)),
    (2, 9, 3, 64, (2, 24), 16, 6, (90, 33)),
    # the served cell's dispatch: 8 slots x 128 pages, two sequences of
    # ~1.4k tokens, six idle slots (fill 1)
    (8, 32, 8, 128, 64, 16, 128, (1400, 1, 1, 1377, 1, 1, 1, 1)),
])
def test_paged_decode_attention_sweep(B, H, Hkv, D, P, ps, N, valids,
                                      dtype):
    """Paged kernel vs oracle across ragged fill levels, page sizes, the
    served models' head layouts, partial blocks and stacked pools (``P``
    as ``(L, P)``, read at layer ``L - 1``); f32 must match to <= 1e-4
    max abs error. A row with no filled token reads zeros."""
    pool = P if isinstance(P, tuple) else (P,)
    layer = pool[0] - 1 if len(pool) == 2 else None
    q = jax.random.normal(KEY, (B, H, D), dtype)
    kp = jax.random.normal(jax.random.PRNGKey(1), (*pool, ps, Hkv, D), dtype)
    vp = jax.random.normal(jax.random.PRNGKey(2), (*pool, ps, Hkv, D), dtype)
    bt = jax.random.randint(jax.random.PRNGKey(3), (B, N), 0, pool[-1])
    valid = jnp.asarray(valids, jnp.int32)
    o = paged_decode_attention(q, kp, vp, bt, valid, layer=layer)
    o_ref = ref.paged_decode_attention(q, kp, vp, bt, valid, D ** -0.5,
                                       layer=layer)
    o_ref = jnp.where((valid > 0)[:, None, None], o_ref, 0)
    tol = _tol(dtype) if dtype == jnp.bfloat16 else \
        dict(rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32), **tol)


def test_paged_matches_dense_on_contiguous_table():
    """A contiguous block table over the pool IS the dense cache: the
    paged oracle must agree with the dense decode oracle exactly."""
    B, H, Hkv, D, P, ps, N = 2, 8, 2, 32, 8, 16, 8
    q = jax.random.normal(KEY, (B, H, D), jnp.float32)
    kp = jax.random.normal(jax.random.PRNGKey(1), (P, ps, Hkv, D))
    vp = jax.random.normal(jax.random.PRNGKey(2), (P, ps, Hkv, D))
    bt = jnp.tile(jnp.arange(N)[None], (B, 1))
    k_dense = jnp.broadcast_to(kp.reshape(1, N * ps, Hkv, D),
                               (B, N * ps, Hkv, D))
    v_dense = jnp.broadcast_to(vp.reshape(1, N * ps, Hkv, D),
                               (B, N * ps, Hkv, D))
    o = paged_decode_attention(q, kp, vp, bt, jnp.full((B,), 77))
    o_ref = ref.decode_attention(q, k_dense, v_dense, 77, D ** -0.5)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=1e-5)


def test_paged_scratch_pages_fully_masked():
    """Table entries past the fill level point at the reserved scratch
    page; whatever garbage lives there must never reach the output."""
    B, H, Hkv, D, P, ps, N = 1, 4, 2, 32, 6, 16, 4
    q = jax.random.normal(KEY, (B, H, D), jnp.float32)
    kp = jax.random.normal(jax.random.PRNGKey(1), (P, ps, Hkv, D))
    vp = jax.random.normal(jax.random.PRNGKey(2), (P, ps, Hkv, D))
    bt = jnp.array([[3, 0, 0, 0]])          # one live page + scratch refs
    o1 = paged_decode_attention(q, kp, vp, bt, jnp.array([9]))
    kp2 = kp.at[0].set(1e4)                 # poison the scratch page
    vp2 = vp.at[0].set(-1e4)
    o2 = paged_decode_attention(q, kp2, vp2, bt, jnp.array([9]))
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,Hkv,D,P,ps,N,T,bq,starts", [
    (1, 4, 2, 32, 8, 16, 4, 16, 16, (16,)),      # one-page chunk mid-seq
    (2, 8, 2, 32, 12, 16, 6, 32, 16, (0, 48)),   # 2-page chunk, q-tile loop
    (1, 4, 4, 32, 6, 8, 6, 16, 8, (24,)),        # small pages, MHA
    (2, 16, 4, 32, 16, 16, 8, 64, 32, (32, 0)),  # long chunk, ragged starts
    (2, 32, 8, 128, 10, 16, 4, 32, 16, (32, 0)),  # qwen3-4b heads: G 4
    (2, 9, 3, 64, 10, 16, 4, 32, None, (16, 32)),  # smollm-135m: G 3
])
def test_chunked_prefill_attention_sweep(B, H, Hkv, D, P, ps, N, T, bq,
                                         starts, dtype):
    """Chunked-prefill kernel vs oracle across chunk lengths, q tiles,
    per-sequence start offsets and the served models' head layouts; f32
    must match to <= 1e-4 max abs error."""
    q = jax.random.normal(KEY, (B, T, H, D), dtype)
    kp = jax.random.normal(jax.random.PRNGKey(1), (P, ps, Hkv, D), dtype)
    vp = jax.random.normal(jax.random.PRNGKey(2), (P, ps, Hkv, D), dtype)
    bt = jax.random.randint(jax.random.PRNGKey(3), (B, N), 0, P)
    sp = jnp.asarray(starts, jnp.int32)
    o = chunked_prefill_attention(q, kp, vp, bt, sp, block_q=bq)
    o_ref = ref.chunked_prefill_attention(q, kp, vp, bt, sp, D ** -0.5)
    tol = _tol(dtype) if dtype == jnp.bfloat16 else \
        dict(rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32), **tol)


def test_chunked_prefill_replays_monolithic_flash():
    """Running a prompt through chunk-sized pieces against a contiguous
    block table reproduces the monolithic causal flash prefill exactly:
    chunk t's rows equal rows [t*ps, (t+1)*ps) of full attention."""
    B, H, Hkv, D, ps, N = 1, 4, 2, 32, 16, 4
    S = N * ps
    q = jax.random.normal(KEY, (B, S, H, D), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, Hkv, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, Hkv, D))
    kp = k[0].reshape(N, ps, Hkv, D)
    vp = v[0].reshape(N, ps, Hkv, D)
    bt = jnp.arange(N)[None]
    # monolithic oracle in kernel layout (BH, S, D)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, S, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, S, D)
    o_full = ref.flash_attention(qf, kf, vf, D ** -0.5, causal=True)
    o_full = o_full.reshape(B, H, S, D).transpose(0, 2, 1, 3)
    for t in range(N):
        o_chunk = chunked_prefill_attention(
            q[:, t * ps:(t + 1) * ps], kp, vp, bt[:, :t + 1],
            jnp.array([t * ps], jnp.int32))
        np.testing.assert_allclose(np.asarray(o_chunk),
                                   np.asarray(o_full[:, t * ps:(t + 1) * ps]),
                                   rtol=1e-5, atol=1e-5)


def test_chunked_prefill_future_pages_fully_masked():
    """Block-table entries past the chunk's causal horizon (scratch refs)
    must never reach the output, whatever garbage lives there."""
    B, H, Hkv, D, P, ps, N = 1, 4, 2, 32, 6, 16, 4
    q = jax.random.normal(KEY, (B, ps, H, D), jnp.float32)
    kp = jax.random.normal(jax.random.PRNGKey(1), (P, ps, Hkv, D))
    vp = jax.random.normal(jax.random.PRNGKey(2), (P, ps, Hkv, D))
    bt = jnp.array([[3, 4, 0, 0]])         # chunk on page 4, future = 0
    start = jnp.array([ps], jnp.int32)
    o1 = chunked_prefill_attention(q, kp, vp, bt, start)
    kp2 = kp.at[0].set(1e4)                # poison the scratch page
    vp2 = vp.at[0].set(-1e4)
    o2 = chunked_prefill_attention(q, kp2, vp2, bt, start)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=0)


@pytest.mark.parametrize("B,S,H,P,N,Q", [
    (2, 128, 4, 32, 16, 32),
    (1, 256, 2, 64, 128, 64),
    (2, 64, 8, 16, 32, 16),
])
def test_ssd_full_sweep(B, S, H, P, N, Q):
    x = jax.random.normal(KEY, (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(1), (B, S, H)))
    a = -jnp.exp(jax.random.normal(jax.random.PRNGKey(2), (H,)) * 0.5)
    B_ = jax.random.normal(jax.random.PRNGKey(3), (B, S, N), jnp.float32)
    C_ = jax.random.normal(jax.random.PRNGKey(4), (B, S, N), jnp.float32)
    y1 = ssd_full(x, dt, a, B_, C_, Q)
    y2 = ref.ssd_full(x, dt, a, B_, C_, Q)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=2e-4,
                               atol=2e-4)


def test_ssd_chunk_pieces_match_ref():
    B, nc, Q, H, P, N = 1, 4, 32, 2, 16, 8
    x = jax.random.normal(KEY, (B, nc, Q, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(1),
                                           (B, nc, Q, H)))
    a = -jnp.exp(jax.random.normal(jax.random.PRNGKey(2), (H,)) * 0.5)
    B_ = jax.random.normal(jax.random.PRNGKey(3), (B, nc, Q, N))
    C_ = jax.random.normal(jax.random.PRNGKey(4), (B, nc, Q, N))
    y1, s1, d1, c1 = ssd_chunk(x, dt, a, B_, C_)
    y2, s2, d2, c2 = ref.ssd_chunk(x, dt, a, B_, C_)
    for u, v in [(y1, y2), (s1, s2), (d1, d2), (c1, c2)]:
        np.testing.assert_allclose(np.asarray(u), np.asarray(v), rtol=2e-4,
                                   atol=2e-4)


@pytest.mark.parametrize("B,S,C,bs,bl,h0flag", [
    (2, 64, 128, 32, 64, False),
    (1, 128, 256, 32, 128, True),
    (3, 32, 512, 16, 256, False),
])
def test_rglru_scan_sweep(B, S, C, bs, bl, h0flag):
    a = jax.nn.sigmoid(jax.random.normal(KEY, (B, S, C)))
    b = jax.random.normal(jax.random.PRNGKey(1), (B, S, C), jnp.float32)
    h0 = (jax.random.normal(jax.random.PRNGKey(2), (B, C))
          if h0flag else None)
    y1 = rglru_scan(a, b, h0, block_seq=bs, block_lanes=bl)
    y2 = ref.rglru_scan(a, b, h0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-5,
                               atol=1e-5)


def test_ops_wrappers():
    """jit'd public wrappers (model-layout shapes)."""
    B, S, H, Hkv, D = 1, 128, 4, 2, 32
    q = jax.random.normal(KEY, (B, S, H, D), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, Hkv, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, Hkv, D))
    o = ops.flash_attention(q, k, v, block_q=64, block_k=64)
    from repro.models.attention import attend_naive
    o_ref = attend_naive(q, k, v, jnp.arange(S), jnp.arange(S), D ** -0.5)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5)

    qd = jax.random.normal(KEY, (B, H, D), jnp.float32)
    od = ops.decode_attention(qd, k, v, 100)
    od_ref = ref.decode_attention(qd, k, v, 100, D ** -0.5)
    np.testing.assert_allclose(np.asarray(od), np.asarray(od_ref), atol=2e-5)

    kp = k.reshape(-1, 16, Hkv, D)
    vp = v.reshape(-1, 16, Hkv, D)
    bt = jnp.arange(kp.shape[0])[None]
    op = ops.paged_decode_attention(qd, kp, vp, bt, jnp.array([100]))
    np.testing.assert_allclose(np.asarray(op), np.asarray(od_ref), atol=2e-5)

    oc = ops.chunked_prefill_attention(q[:, -16:], kp, vp, bt,
                                       jnp.array([S - 16]))
    oc_ref = ref.chunked_prefill_attention(q[:, -16:], kp, vp, bt,
                                           jnp.array([S - 16]), D ** -0.5)
    np.testing.assert_allclose(np.asarray(oc), np.asarray(oc_ref), atol=2e-5)


def test_model_ssm_block_matches_kernel_path():
    """The model's XLA SSD (models.ssm.ssd_chunked) vs the Pallas ssd_full."""
    from repro.models.ssm import ssd_chunked
    B, S, H, P, N, Q = 1, 64, 2, 16, 8, 16
    x = jax.random.normal(KEY, (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(1), (B, S, H)))
    a = -jnp.exp(jax.random.normal(jax.random.PRNGKey(2), (H,)) * 0.3)
    B_ = jax.random.normal(jax.random.PRNGKey(3), (B, S, N))
    C_ = jax.random.normal(jax.random.PRNGKey(4), (B, S, N))
    y_model, _ = ssd_chunked(x, dt, a, B_, C_, Q)
    y_kernel = ssd_full(x, dt, a, B_, C_, Q)
    np.testing.assert_allclose(np.asarray(y_model), np.asarray(y_kernel),
                               rtol=2e-4, atol=2e-4)
