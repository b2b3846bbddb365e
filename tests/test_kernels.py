"""Per-kernel shape/dtype sweeps, asserting allclose against the ref.py
pure-jnp oracles (kernels execute in interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _mha_via_ref(q, k, v, window):
    b, sq, h, d = q.shape
    _, sk, hkv, _ = k.shape
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, sk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, sk, d)
    out = ref.mha_reference(qf, kf, vf, window=window)
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)


@pytest.mark.parametrize("b,s,h,hkv,d,win", [
    (2, 128, 4, 2, 64, 0),
    pytest.param(1, 256, 2, 2, 32, 0, marks=pytest.mark.slow),
    pytest.param(2, 128, 8, 1, 64, 0, marks=pytest.mark.slow),     # MQA
    pytest.param(1, 256, 4, 4, 64, 64, marks=pytest.mark.slow),    # sliding window
    pytest.param(1, 128, 4, 2, 128, 16, marks=pytest.mark.slow),
])
@pytest.mark.parametrize("dtype", [jnp.float32,
                                   pytest.param(jnp.bfloat16,
                                                marks=pytest.mark.slow)])
def test_flash_attention_matches_reference(b, s, h, hkv, d, win, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, d)).astype(dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, d)).astype(dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, d)).astype(dtype)
    out = ops.flash_attention(q, k, v, window=win, block_q=64, block_k=64,
                              interpret=True)
    expect = _mha_via_ref(q, k, v, win)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("block_q,block_k", [
    (64, 64),
    pytest.param(32, 64, marks=pytest.mark.slow),
    pytest.param(128, 32, marks=pytest.mark.slow),
])
def test_flash_attention_block_shapes(block_q, block_k):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 64))
    k = jax.random.normal(ks[1], (1, 128, 2, 64))
    v = jax.random.normal(ks[2], (1, 128, 2, 64))
    out = ops.flash_attention(q, k, v, block_q=block_q, block_k=block_k,
                              interpret=True)
    expect = _mha_via_ref(q, k, v, 0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=2e-5)


@pytest.mark.parametrize("t,d,v,bt,bv", [
    (256, 64, 512, 64, 128),
    pytest.param(128, 128, 1000, 128, 250, marks=pytest.mark.slow),
    pytest.param(512, 32, 64, 256, 64, marks=pytest.mark.slow),
])
@pytest.mark.parametrize("dtype", [jnp.float32,
                                   pytest.param(jnp.bfloat16,
                                                marks=pytest.mark.slow)])
def test_fused_xent_matches_reference(t, d, v, bt, bv, dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    h = jax.random.normal(ks[0], (t, d)).astype(dtype)
    w = (jax.random.normal(ks[1], (d, v)) * 0.05).astype(dtype)
    labels = jax.random.randint(ks[2], (t,), 0, v)
    got = ops.fused_cross_entropy(h, w, labels, block_t=bt, block_v=bv,
                                  interpret=True)
    expect = ref.xent_reference(h, w, labels).mean()
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(float(got), float(expect), atol=tol, rtol=tol)


def test_fused_xent_label_edge_cases():
    # labels at vocab block boundaries must hit exactly one panel
    t, d, v = 64, 32, 256
    h = jax.random.normal(jax.random.PRNGKey(3), (t, d))
    w = jax.random.normal(jax.random.PRNGKey(4), (d, v)) * 0.1
    labels = jnp.concatenate([jnp.zeros(16, jnp.int32),
                              jnp.full((16,), 63, jnp.int32),
                              jnp.full((16,), 64, jnp.int32),
                              jnp.full((16,), 255, jnp.int32)])
    got = ops.fused_cross_entropy(h, w, labels, block_t=32, block_v=64,
                                  interpret=True)
    expect = ref.xent_reference(h, w, labels).mean()
    np.testing.assert_allclose(float(got), float(expect), rtol=1e-5)


@pytest.mark.parametrize("n,d", [
    (256, 32),
    pytest.param(512, 128, marks=pytest.mark.slow),
    pytest.param(64, 64, marks=pytest.mark.slow),
    (3000, 32),      # D_o = 3000 (Table II) at the MNIST cut width: ragged
    (3000, 256),     # ... and at the CIFAR-10 cut width
    (1001, 64),      # N not a multiple of 8
])
@pytest.mark.parametrize("dtype", [jnp.float32,
                                   pytest.param(jnp.bfloat16,
                                                marks=pytest.mark.slow)])
def test_tamper_distance_matches_reference(n, d, dtype):
    a = jax.random.normal(jax.random.PRNGKey(5), (n, d)).astype(dtype)
    b = a + 0.05 * jax.random.normal(jax.random.PRNGKey(6), (n, d)).astype(dtype)
    got = ops.tamper_distance(a, b, block_n=64, interpret=True)
    s = ref.tamper_sums_reference(a, b)
    expect = jnp.sqrt(s[0]) / jnp.sqrt(s[1])
    np.testing.assert_allclose(float(got), float(expect), rtol=2e-2)


def test_tamper_distance_identical_is_zero():
    a = jax.random.normal(jax.random.PRNGKey(7), (128, 64))
    assert float(ops.tamper_distance(a, a, interpret=True)) == 0.0


# ---------------------------------------------------------------------------
# flash-decoding kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,h,hkv,d,win,idx", [
    (2, 256, 4, 2, 64, 0, 255),
    pytest.param(1, 512, 4, 1, 64, 0, 100,
                 marks=pytest.mark.slow),      # partially-filled cache
    pytest.param(2, 256, 2, 2, 32, 64, 200,
                 marks=pytest.mark.slow),      # sliding window
    pytest.param(1, 1024, 8, 2, 128, 0, 1023, marks=pytest.mark.slow),
])
def test_decode_attention_matches_reference(b, s, h, hkv, d, win, idx):
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = jax.random.normal(ks[0], (b, 1, h, d))
    k = jax.random.normal(ks[1], (b, s, hkv, d))
    v = jax.random.normal(ks[2], (b, s, hkv, d))
    got = ops.decode_attention(q, k, v, idx, window=win, block_k=128,
                               interpret=True)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, 1, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    expect = ref.decode_attention_reference(qf, kf, vf, idx, window=win)
    expect = expect.reshape(b, h, 1, d).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect), atol=2e-5)


def test_decode_attention_matches_model_gqa_decode():
    """The kernel must agree with the model's XLA decode-attention path."""
    from repro.models import attention as attn
    cfg = attn.AttnConfig(d_model=64, n_heads=4, n_kv_heads=2, head_dim=16)
    b, s, idx = 2, 64, 33
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (b, 1, 4, 16))
    k = jax.random.normal(ks[1], (b, s, 2, 16))
    v = jax.random.normal(ks[2], (b, s, 2, 16))
    got = ops.decode_attention(q, k, v, idx, block_k=32, interpret=True)
    valid = jnp.arange(s) <= idx
    groups = 4 // 2
    k_all = attn._repeat_kv(k, groups)
    v_all = attn._repeat_kv(v, groups)
    expect = attn.attend(q, k_all, v_all, valid[None, :], 1.0 / 4.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect), atol=2e-5)


# ---------------------------------------------------------------------------
# fused sLSTM scan kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t,b,d,h", [(16, 2, 32, 2), (32, 1, 64, 4),
                                     (8, 4, 16, 1)])
@pytest.mark.slow
def test_slstm_kernel_matches_reference(t, b, d, h):
    ks = jax.random.split(jax.random.PRNGKey(10), 2)
    pre = jax.random.normal(ks[0], (t, b, 4 * d)) * 0.5
    dh = d // h
    r = jax.random.normal(ks[1], (h, dh, 4 * dh)) / np.sqrt(dh)
    got = ops.slstm_scan(pre, r, n_heads=h, interpret=True)
    expect = ref.slstm_scan_reference(pre, r, n_heads=h)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect), atol=2e-4)


def test_slstm_kernel_matches_model_layer():
    """Kernel vs the model's slstm_forward inner recurrence (same gating)."""
    from repro.models import xlstm as xl
    cfg = xl.XLSTMConfig(d_model=32, n_heads=2)
    p = xl.slstm_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 32)) * 0.5
    from repro.models.blocks import linear
    pre = linear(p["w_in"], x).swapaxes(0, 1)            # (T, B, 4d)
    hs = ops.slstm_scan(pre, p["r"], n_heads=2, interpret=True)
    # model's forward applies out_norm+down afterwards; compare raw h by
    # reproducing the reference directly
    expect = ref.slstm_scan_reference(pre, p["r"], n_heads=2)
    np.testing.assert_allclose(np.asarray(hs), np.asarray(expect), atol=2e-4)
