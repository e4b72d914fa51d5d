"""Per-kernel correctness: Pallas (interpret mode) vs the pure-jnp oracles.

Each kernel is swept over shapes and dtypes per the deliverable requirement;
the jnp "fast paths" used on CPU (flash scan, chunked SSD) are themselves
validated against the naive references.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import block_sizes, flash_attention
from repro.kernels.fused_update import (adamw_epilogue, fused_axpy,
                                        fused_dot_norms, sgd_epilogue)
from repro.kernels.mamba2_scan import mamba2_chunked
from repro.kernels.rwkv6_scan import rwkv6_chunked
from repro.kernels.sam_perturb import sam_perturb, sq_norm

KEY = jax.random.PRNGKey(0)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,kv,hd", [
    (1, 128, 4, 4, 64),     # MHA
    (2, 256, 4, 2, 64),     # GQA
    (1, 128, 8, 1, 128),    # MQA, bigger head
    (2, 128, 4, 4, 32),
])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 64), (False, None)])
def test_flash_attention_pallas_vs_reference(b, s, h, kv, hd, dtype, causal, window):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, h, hd), dtype)
    k = jax.random.normal(ks[1], (b, s, kv, hd), dtype)
    v = jax.random.normal(ks[2], (b, s, kv, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=64, block_k=64, interpret=True)
    expect = ref.mha_reference(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **_tol(dtype))


# the tiles `block_sizes` chooses (no block_q / block_k given)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,kv,hd,hd_v,window", [
    (1, 1024, 2, 2, 64, 64, None),    # two blocks a side: clamped k/v index
    (1, 1024, 2, 2, 64, 64, 200),     # window not a multiple of the block
    (1, 512, 2, 2, 64, 64, 64),       # window narrower than the block
    (2, 1024, 4, 2, 64, 64, None),    # GQA
    (1, 1024, 2, 2, 96, 96, None),    # phi-3 head dim
    (1, 1024, 2, 1, 256, 256, None),  # gemma-2b head dim, MQA
    (1, 1024, 2, 2, 192, 128, None),  # MLA: value head narrower than q/k
], ids=["causal", "window200", "window64", "gqa", "hd96", "hd256", "mla"])
def test_flash_attention_chosen_tiles_vs_reference(b, s, h, kv, hd, hd_v,
                                                   window, dtype):
    bq, bk = block_sizes(s, s, window)
    # several blocks a side: fully masked steps, whose k/v index is clamped
    assert s // bq >= 2 and s // bk >= 2 and s % bq == 0 and s % bk == 0
    if window is not None:
        assert bq <= max(window, 128) and bk <= max(window, 128)
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, h, hd), dtype)
    k = jax.random.normal(ks[1], (b, s, kv, hd), dtype)
    v = jax.random.normal(ks[2], (b, s, kv, hd_v), dtype)
    out = flash_attention(q, k, v, causal=True, window=window, interpret=True)
    expect = ref.mha_reference(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **_tol(dtype))


@pytest.mark.parametrize("sq,sk,window,want", [
    (2048, 2048, None, (512, 512)),     # olmo-1b
    (8192, 8192, None, (512, 512)),     # gemma-2b
    (4096, 4096, None, (512, 512)),     # phi-3-vision
    (2048, 2048, None, (512, 512)),     # whisper, train stress shape
    (384, 1024, None, (128, 512)),      # cross-attention, sq != sk
    (4096, 4096, 4096, (512, 512)),     # mixtral's window
    (2048, 2048, 300, (256, 256)),      # a window narrower than 512
    (2048, 2048, 64, (128, 128)),       # a window narrower than 128
    (64, 64, None, (64, 64)),           # one short block
    (1500, 1500, None, (128, 128)),     # whisper encoder: refused below
], ids=["olmo", "gemma", "phi3", "whisper", "cross", "mixtral", "window300",
        "window64", "short", "whisper_encoder"])
def test_flash_attention_block_sizes(sq, sk, window, want):
    assert block_sizes(sq, sk, window) == want


def test_flash_attention_refuses_a_sequence_no_tile_divides():
    q = jnp.zeros((1, 1500, 2, 64), jnp.bfloat16)
    with pytest.raises(AssertionError):
        flash_attention(q, q, q, causal=False, interpret=True)


@pytest.mark.parametrize("s,kv_block", [(256, 64), (512, 128)])
def test_flash_jnp_scan_vs_naive(s, kv_block):
    """The CPU/dry-run fast path is FLOP- and value-equivalent to naive."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, s, 4, 64))
    k = jax.random.normal(ks[1], (2, s, 2, 64))
    v = jax.random.normal(ks[2], (2, s, 2, 64))
    out = ref.flash_attention_jnp(q, k, v, causal=True, kv_block=kv_block)
    expect = ref.mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out, expect, rtol=2e-5, atol=2e-5)


def test_flash_mla_unequal_value_dim():
    """MLA decompressed attention: qk dim 48, v dim 32."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 128, 4, 48))
    k = jax.random.normal(ks[1], (2, 128, 4, 48))
    v = jax.random.normal(ks[2], (2, 128, 4, 32))
    out = ref.flash_attention_jnp(q, k, v, causal=True, kv_block=64)
    expect = ref.mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out, expect, rtol=2e-5, atol=2e-5)


def test_decode_attention_matches_masked_reference():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 1, 4, 64))
    k = jax.random.normal(ks[1], (2, 64, 2, 64))
    v = jax.random.normal(ks[2], (2, 64, 2, 64))
    valid = jnp.asarray(40)
    out = ref.decode_attention_jnp(q, k, v, valid)
    expect = ref.mha_reference(q, k[:, :40], v[:, :40], causal=False)
    np.testing.assert_allclose(out, expect, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# sam perturb
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1000, 65536, 200_001])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sam_perturb_kernel(n, dtype):
    ks = jax.random.split(KEY, 2)
    w = jax.random.normal(ks[0], (n,), dtype)
    g = jax.random.normal(ks[1], (n,), jnp.float32)
    sn = sq_norm(g, interpret=True)
    assert float(sn) == pytest.approx(float(jnp.sum(g * g)), rel=1e-5)
    out = sam_perturb(w, g, 0.1, sn, interpret=True)
    expect = ref.sam_perturb_flat_jnp(w.astype(jnp.float32), g,
                                      jnp.float32(0.1), sn).astype(dtype)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **_tol(dtype))


# ---------------------------------------------------------------------------
# fused weight-space epilogue (flat-buffer update path)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1000, 200_001])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_axpy_kernel(n, dtype):
    ks = jax.random.split(KEY, 2)
    y = jax.random.normal(ks[0], (n,), dtype)
    x = jax.random.normal(ks[1], (n,), jnp.float32)
    out = fused_axpy(0.37, x, y, interpret=True)
    expect = ref.axpy_flat_jnp(0.37, x, y)
    assert out.dtype == y.dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **_tol(dtype))


@pytest.mark.parametrize("n", [1000, 65536, 200_001])
def test_fused_dot_norms_kernel(n):
    ks = jax.random.split(KEY, 2)
    a = jax.random.normal(ks[0], (n,))
    b = jax.random.normal(ks[1], (n,))
    got = fused_dot_norms(a, b, interpret=True)
    expect = ref.dot_norms_flat_jnp(a, b)
    for g, e in zip(got, expect):
        np.testing.assert_allclose(float(g), float(e), rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("momentum,nesterov,wd", [
    (0.9, False, 0.0),
    (0.9, True, 1e-4),
    (0.0, False, 5e-4),
])
def test_sgd_epilogue_kernel(momentum, nesterov, wd, dtype, n=200_001):
    ks = jax.random.split(KEY, 3)
    w = jax.random.normal(ks[0], (n,), dtype)
    g = jax.random.normal(ks[1], (n,), jnp.float32)
    m = jax.random.normal(ks[2], (n,), jnp.float32) if momentum else None
    w_k, m_k = sgd_epilogue(w, g, m, 0.7, 0.1, momentum=momentum,
                            nesterov=nesterov, weight_decay=wd, interpret=True)
    w_r, m_r = ref.sgd_epilogue_flat_jnp(w, g, m, 0.7, 0.1, momentum=momentum,
                                         nesterov=nesterov, weight_decay=wd)
    assert w_k.dtype == w.dtype
    np.testing.assert_allclose(np.asarray(w_k, np.float32),
                               np.asarray(w_r, np.float32), **_tol(dtype))
    if momentum:
        np.testing.assert_allclose(m_k, m_r, rtol=2e-5, atol=2e-5)
    else:
        assert m_k is None and m_r is None


@pytest.mark.parametrize("n", [1000, 65536, 200_001])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_delta_amax_kernel(n, dtype):
    from repro.kernels.fused_update import delta_amax
    ks = jax.random.split(KEY, 3)
    p = jax.random.normal(ks[0], (n,), dtype)
    s = jax.random.normal(ks[1], (n,), jnp.float32)
    e = 0.01 * jax.random.normal(ks[2], (n,), jnp.float32)
    got = delta_amax(p, s, e, interpret=True)
    expect = ref.delta_amax_flat_jnp(p, s, e)
    np.testing.assert_allclose(float(got), float(expect), rtol=1e-6)


@pytest.mark.parametrize("n", [1000, 200_001])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_delta_encode_i8_kernel(n, dtype):
    from repro.kernels.fused_update import delta_amax, delta_encode_i8
    ks = jax.random.split(KEY, 3)
    p = jax.random.normal(ks[0], (n,), dtype)
    s = jax.random.normal(ks[1], (n,), jnp.float32)
    e = 0.01 * jax.random.normal(ks[2], (n,), jnp.float32)
    from repro.service.delta import _pow2_scale
    scale = _pow2_scale(float(delta_amax(p, s, e, interpret=True)))
    q_k, s_k, e_k = delta_encode_i8(p, s, e, scale, interpret=True)
    q_r, s_r, e_r = ref.delta_encode_i8_flat_jnp(p, s, e, scale)
    assert q_k.dtype == jnp.int8 and s_k.dtype == jnp.float32
    # with the power-of-two scale the int8 payload AND the shadow advance
    # must match the oracle bit for bit (q * scale is exact in fp32, so FMA
    # contraction cannot skew the result) — that is the property that keeps
    # the client's and the server's shadows identical
    np.testing.assert_array_equal(np.asarray(q_k), np.asarray(q_r))
    np.testing.assert_array_equal(np.asarray(s_k), np.asarray(s_r))
    np.testing.assert_array_equal(np.asarray(e_k), np.asarray(e_r))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("wd", [0.0, 0.01])
def test_adamw_epilogue_kernel(wd, dtype, n=200_001):
    ks = jax.random.split(KEY, 4)
    w = jax.random.normal(ks[0], (n,), dtype)
    g = jax.random.normal(ks[1], (n,), jnp.float32)
    mu = jax.random.normal(ks[2], (n,), jnp.float32)
    nu = jnp.abs(jax.random.normal(ks[3], (n,), jnp.float32))
    args = (w, g, mu, nu, 0.7, 0.01, 0.1, 0.001)
    got = adamw_epilogue(*args, weight_decay=wd, interpret=True)
    expect = ref.adamw_epilogue_flat_jnp(*args, weight_decay=wd)
    assert got[0].dtype == w.dtype
    np.testing.assert_allclose(np.asarray(got[0], np.float32),
                               np.asarray(expect[0], np.float32), **_tol(dtype))
    for g_k, g_r in zip(got[1:], expect[1:]):
        np.testing.assert_allclose(g_k, g_r, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# mamba2 chunked SSD
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s,chunk", [(128, 32), (256, 64)])
@pytest.mark.parametrize("h,p,g,n", [(4, 32, 1, 16), (2, 16, 2, 16)])
def test_mamba2_pallas_vs_sequential(s, chunk, h, p, g, n):
    ks = jax.random.split(KEY, 4)
    B = 2
    x = jax.random.normal(ks[0], (B, s, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, s, h)))
    a = -jnp.exp(jnp.linspace(-1.0, 1.0, h))
    b = jax.random.normal(ks[2], (B, s, g, n)) * 0.3
    c = jax.random.normal(ks[3], (B, s, g, n)) * 0.3
    d = jnp.full((h,), 0.5)
    y_k, h_k = mamba2_chunked(x, dt, a, b, c, d, chunk=chunk, interpret=True)
    y_r, h_r = ref.mamba2_scan_ref(x, dt, a, b, c, d)
    np.testing.assert_allclose(y_k, y_r, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(h_k, h_r, rtol=2e-4, atol=2e-4)


def test_mamba2_chunked_jnp_vs_sequential():
    ks = jax.random.split(KEY, 4)
    B, s, h, p, g, n = 2, 128, 4, 16, 1, 8
    x = jax.random.normal(ks[0], (B, s, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, s, h)))
    a = -jnp.exp(jnp.linspace(-1.0, 1.0, h))
    b = jax.random.normal(ks[2], (B, s, g, n)) * 0.3
    c = jax.random.normal(ks[3], (B, s, g, n)) * 0.3
    d = jnp.full((h,), 0.5)
    y_c, h_c = ref.mamba2_chunked_jnp(x, dt, a, b, c, d, chunk=32)
    y_r, h_r = ref.mamba2_scan_ref(x, dt, a, b, c, d)
    np.testing.assert_allclose(y_c, y_r, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(h_c, h_r, rtol=2e-4, atol=2e-4)


def test_mamba2_state_continuation():
    """Splitting a sequence across two scans with carried state == one scan."""
    ks = jax.random.split(KEY, 4)
    B, s, h, p, g, n = 1, 64, 2, 8, 1, 8
    x = jax.random.normal(ks[0], (B, s, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, s, h)))
    a = -jnp.exp(jnp.linspace(-1.0, 0.0, h))
    b = jax.random.normal(ks[2], (B, s, g, n)) * 0.3
    c = jax.random.normal(ks[3], (B, s, g, n)) * 0.3
    d = jnp.zeros((h,))
    y_full, h_full = ref.mamba2_scan_ref(x, dt, a, b, c, d)
    y1, h1 = ref.mamba2_scan_ref(x[:, :32], dt[:, :32], a, b[:, :32], c[:, :32], d)
    y2, h2 = ref.mamba2_scan_ref(x[:, 32:], dt[:, 32:], a, b[:, 32:], c[:, 32:],
                                 d, init_state=h1)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), y_full,
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(h2, h_full, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# rwkv6 wkv
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s,chunk", [(64, 16), (128, 32)])
@pytest.mark.parametrize("k,v", [(16, 16), (32, 32)])
def test_rwkv6_pallas_vs_sequential(s, chunk, k, v):
    ks = jax.random.split(KEY, 5)
    B, H = 2, 2
    r = jax.random.normal(ks[0], (B, s, H, k)) * 0.5
    kk = jax.random.normal(ks[1], (B, s, H, k)) * 0.5
    vv = jax.random.normal(ks[2], (B, s, H, v)) * 0.5
    w = -jnp.exp(jax.random.normal(ks[3], (B, s, H, k)) * 0.5 - 2.0)
    u = jax.random.normal(ks[4], (H, k)) * 0.1
    y_k, s_k = rwkv6_chunked(r, kk, vv, w, u, chunk=chunk, interpret=True)
    y_r, s_r = ref.rwkv6_scan_ref(r, kk, vv, w, u)
    np.testing.assert_allclose(y_k, y_r, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s_k, s_r, rtol=1e-5, atol=1e-5)


def test_rwkv6_state_continuation():
    ks = jax.random.split(KEY, 5)
    B, s, H, k = 1, 64, 2, 8
    r = jax.random.normal(ks[0], (B, s, H, k)) * 0.5
    kk = jax.random.normal(ks[1], (B, s, H, k)) * 0.5
    vv = jax.random.normal(ks[2], (B, s, H, k)) * 0.5
    w = -jnp.exp(jax.random.normal(ks[3], (B, s, H, k)) * 0.3 - 2.0)
    u = jax.random.normal(ks[4], (H, k)) * 0.1
    y_full, s_full = ref.rwkv6_scan_ref(r, kk, vv, w, u)
    y1, s1 = ref.rwkv6_scan_ref(r[:, :32], kk[:, :32], vv[:, :32], w[:, :32], u)
    y2, s2 = ref.rwkv6_scan_ref(r[:, 32:], kk[:, 32:], vv[:, 32:], w[:, 32:], u,
                                init_state=s1)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), y_full,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s2, s_full, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# training through the sequence kernels: the reference-VJP backward
# ---------------------------------------------------------------------------

def _attention_args(ks):
    return (jax.random.normal(ks[0], (1, 128, 4, 32)),
            jax.random.normal(ks[1], (1, 128, 2, 32)),
            jax.random.normal(ks[2], (1, 128, 2, 32)))


def _mamba2_args(ks):
    B, s, h, p, g, n = 2, 64, 2, 16, 1, 16
    return (jax.random.normal(ks[0], (B, s, h, p)) * 0.5,
            jax.nn.softplus(jax.random.normal(ks[1], (B, s, h))),
            -jnp.exp(jnp.linspace(-1.0, 1.0, h)),
            jax.random.normal(ks[2], (B, s, g, n)) * 0.3,
            jax.random.normal(ks[3], (B, s, g, n)) * 0.3,
            jnp.full((h,), 0.5))


def _rwkv6_args(ks):
    B, s, H, k = 2, 64, 2, 16
    return (jax.random.normal(ks[0], (B, s, H, k)) * 0.5,
            jax.random.normal(ks[1], (B, s, H, k)) * 0.5,
            jax.random.normal(ks[2], (B, s, H, k)) * 0.5,
            -jnp.exp(jax.random.normal(ks[3], (B, s, H, k)) * 0.5 - 2.0),
            jax.random.normal(ks[4], (H, k)) * 0.1)


SEQUENCE_KERNELS = {
    # name: (ops entry point, independent oracle, inputs)
    "flash_attention": (
        lambda q, k, v, impl: ops.flash_attention(q, k, v, window=64,
                                                  impl=impl),
        lambda q, k, v: ref.mha_reference(q, k, v, window=64),
        _attention_args),
    "mamba2": (lambda *a, impl: ops.mamba2_mix(*a, chunk=32, impl=impl),
               ref.mamba2_scan_ref, _mamba2_args),
    "rwkv6": (lambda *a, impl: ops.rwkv6_mix(*a, impl=impl),
              ref.rwkv6_scan_ref, _rwkv6_args),
}


@pytest.mark.parametrize("name", sorted(SEQUENCE_KERNELS))
def test_sequence_kernel_gradients_match_reference(name):
    """jax.grad through the Pallas kernel (interpret mode) equals the
    gradient of the plain reference, for every input and every output."""
    op, oracle, make = SEQUENCE_KERNELS[name]
    args = make(jax.random.split(KEY, 6))
    argnums = tuple(range(len(args)))

    def loss(fn):
        return lambda *a: sum(jnp.sum(jnp.sin(o.astype(jnp.float32)))
                              for o in jax.tree.leaves(fn(*a)))

    got = jax.grad(loss(lambda *a: op(*a, impl="pallas_interpret")),
                   argnums)(*args)
    want = jax.grad(loss(oracle), argnums)(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("name", sorted(SEQUENCE_KERNELS))
def test_sequence_kernel_gradients_under_mesh(name, subprocess_py):
    """On a (2, 2) data x model mesh the kernels run per device in a
    shard_map; the gradient still equals the reference's. A dim that does
    not split (the batch-1 attention) is replicated, with a warning."""
    import pathlib
    out = subprocess_py(f"""
        import logging, sys
        sys.path.insert(0, {str(pathlib.Path(__file__).parent)!r})
        logging.basicConfig(level=logging.WARNING, stream=sys.stdout)
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_host_mesh
        from test_kernels import KEY, SEQUENCE_KERNELS
        op, oracle, make = SEQUENCE_KERNELS[{name!r}]
        args = make(jax.random.split(KEY, 6))
        argnums = tuple(range(len(args)))

        def loss(fn):
            return lambda *a: sum(jnp.sum(jnp.sin(o.astype(jnp.float32)))
                                  for o in jax.tree.leaves(fn(*a)))

        grad = jax.grad(loss(lambda *a: op(*a, impl="pallas_interpret")),
                        argnums)
        with jax.set_mesh(make_host_mesh(model_axis=2)):
            assert "shard_map" in str(jax.make_jaxpr(grad)(*args))
            got = jax.jit(grad)(*args)
        want = jax.grad(loss(oracle), argnums)(*args)
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4)
        print("GRADS_MATCH")
    """, devices=4)
    assert "GRADS_MATCH" in out
    assert ("replicated" in out) == (name == "flash_attention"), out
