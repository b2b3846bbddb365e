"""Model substrate unit tests: chunked-vs-reference paths, decode-vs-forward
consistency, split/merge invariants for every architecture family."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.config import ModelConfig
from repro.models.model import build_model

B, S, V = 2, 16, 97


def _batch():
    return {"tokens": jnp.arange(B * S).reshape(B, S) % V,
            "labels": jnp.ones((B, S), jnp.int32)}


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_chunked_attention_matches_reference():
    cfg = attn.AttnConfig(d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
                          q_chunk=8)
    p = attn.gqa_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64))
    full = attn.gqa_forward(p, cfg._replace(q_chunk=0), x)
    chunked = attn.gqa_forward(p, cfg, x)
    np.testing.assert_allclose(np.asarray(full), np.asarray(chunked), atol=1e-5)


def test_sliding_window_masks_distant_tokens():
    cfg = attn.AttnConfig(d_model=32, n_heads=2, n_kv_heads=2, head_dim=16,
                          sliding_window=4)
    p = attn.gqa_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 24, 32))
    y1 = attn.gqa_forward(p, cfg, x)
    # perturbing a token >window positions before the last must not change it
    x2 = x.at[:, 5].set(jax.random.normal(jax.random.PRNGKey(2), (1, 32)))
    y2 = attn.gqa_forward(p, cfg, x2)
    np.testing.assert_allclose(np.asarray(y1[:, -1]), np.asarray(y2[:, -1]),
                               atol=1e-5)


@pytest.mark.slow
def test_mla_decode_matches_forward():
    cfg = attn.MLAConfig(d_model=64, n_heads=4, head_dim=16, kv_lora_rank=32,
                         rope_dim=16)
    p = attn.mla_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 64))
    full = attn.mla_forward(p, cfg, x)
    cache = attn.init_mla_cache(2, 12, cfg)
    outs = []
    for i in range(12):
        y, cache = attn.mla_decode(p, cfg, x[:, i:i+1], cache, i)
        outs.append(y)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, 1)),
                               np.asarray(full), atol=1e-4)


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_moe_matches_loop_reference_with_ample_capacity():
    cfg = moe_mod.MoEConfig(d_model=32, d_expert=16, n_experts=4, top_k=2,
                            n_shared=1, capacity_factor=8.0)
    p = moe_mod.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32))
    got, aux1 = moe_mod.moe_forward(p, cfg, x)
    expect, aux2 = moe_mod.moe_forward_reference(p, cfg, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect), atol=1e-4)
    np.testing.assert_allclose(float(aux1), float(aux2), rtol=1e-6)


@pytest.mark.slow
def test_moe_local_dispatch_matches_reference():
    """The shard-local dispatch formulation (§Perf) is numerically the same
    computation when capacity is ample."""
    cfg = moe_mod.MoEConfig(d_model=32, d_expert=16, n_experts=4, top_k=2,
                            n_shared=1, capacity_factor=8.0, shard=False,
                            shard_groups=4)
    p = moe_mod.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))
    got, aux1 = moe_mod.moe_forward(p, cfg, x)
    expect, aux2 = moe_mod.moe_forward_reference(
        p, cfg._replace(shard_groups=0), x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect), atol=1e-4)
    np.testing.assert_allclose(float(aux1), float(aux2), rtol=1e-6)


@pytest.mark.slow
def test_moe_capacity_drops_tokens_gracefully():
    cfg = moe_mod.MoEConfig(d_model=16, d_expert=8, n_experts=2, top_k=1,
                            capacity_factor=0.25)
    p = moe_mod.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 16))
    out, _ = moe_mod.moe_forward(p, cfg, x)
    assert out.shape == x.shape
    assert not bool(jnp.any(jnp.isnan(out)))


def test_moe_router_weights_normalized():
    cfg = moe_mod.MoEConfig(d_model=16, d_expert=8, n_experts=4, top_k=2)
    p = moe_mod.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16))
    w, ids, aux = moe_mod.route(p, cfg, x)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), np.ones(8), atol=1e-5)
    assert bool(jnp.all(ids < cfg.n_experts))


# ---------------------------------------------------------------------------
# SSM / xLSTM: chunked parallel form == step-by-step recurrence
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_mamba2_chunked_matches_recurrent():
    cfg = ssm_mod.SSMConfig(d_model=32, d_state=8, chunk=4)
    p = ssm_mod.mamba2_init(jax.random.PRNGKey(0), cfg)
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32)) * 0.5
    par = ssm_mod.mamba2_forward(p, cfg, u)
    rec = ssm_mod.mamba2_forward_reference(p, cfg, u)
    np.testing.assert_allclose(np.asarray(par), np.asarray(rec), atol=1e-4)


@pytest.mark.slow
def test_mlstm_chunked_matches_recurrent():
    cfg = xlstm_mod.XLSTMConfig(d_model=32, n_heads=2, chunk=4)
    p = xlstm_mod.mlstm_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32)) * 0.5
    par = xlstm_mod.mlstm_forward(p, cfg, x)
    rec = xlstm_mod.mlstm_forward_reference(p, cfg, x)
    np.testing.assert_allclose(np.asarray(par), np.asarray(rec), atol=1e-4)


def test_slstm_decode_matches_forward():
    cfg = xlstm_mod.XLSTMConfig(d_model=32, n_heads=2)
    p = xlstm_mod.slstm_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 10, 32)) * 0.5
    full = xlstm_mod.slstm_forward(p, cfg, x)
    cache = xlstm_mod.init_slstm_cache(2, cfg)
    outs = []
    for t in range(10):
        y, cache = xlstm_mod.slstm_decode(p, cfg, x[:, t:t+1], cache)
        outs.append(y)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, 1)),
                               np.asarray(full), atol=1e-4)


# ---------------------------------------------------------------------------
# whole-model split / merge / decode invariants
# ---------------------------------------------------------------------------

FAMILY_CFGS = [
    ModelConfig(name="d", arch_type="dense", n_layers=2, d_model=64, n_heads=4,
                n_kv_heads=2, d_ff=128, vocab=V, qk_norm=True, qkv_bias=True,
                sliding_window=8, global_every=2, cut_layer=1),
    ModelConfig(name="m", arch_type="moe", n_layers=2, d_model=64, n_heads=4,
                n_kv_heads=4, d_ff=128, vocab=V, n_experts=4, top_k=2,
                d_expert=32, first_dense=1, capacity_factor=4.0, cut_layer=1),
    ModelConfig(name="s", arch_type="ssm", n_layers=2, d_model=64, n_heads=4,
                n_kv_heads=4, d_ff=0, vocab=V, ssm_state=16, ssm_chunk=8,
                cut_layer=1),
    ModelConfig(name="h", arch_type="hybrid", n_layers=5, d_model=64, n_heads=4,
                n_kv_heads=4, d_ff=0, vocab=V, ssm_state=16, ssm_chunk=8,
                attn_every=2, cut_layer=3),
]


@pytest.mark.parametrize("cfg", FAMILY_CFGS, ids=lambda c: c.arch_type)
@pytest.mark.slow
def test_split_forward_equals_full_forward(cfg):
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    batch = _batch()
    loss, metrics = m.loss(params, batch)
    gamma, phi = m.split_params(params)
    acts = m.client_forward(gamma, batch)
    loss2, metrics2 = m.ap_forward(phi, acts, batch)
    # client-side MoE aux loss is (correctly) not recoverable by the AP;
    # compare the LM component which must match exactly
    np.testing.assert_allclose(float(metrics["lm_loss"]),
                               float(metrics2["lm_loss"]), atol=1e-5)
    merged = m.merge_params(gamma, phi)
    loss3, _ = m.loss(merged, batch)
    np.testing.assert_allclose(float(loss), float(loss3), atol=1e-6)


@pytest.mark.parametrize("cfg", FAMILY_CFGS, ids=lambda c: c.arch_type)
@pytest.mark.slow
def test_decode_matches_forward(cfg):
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    batch = _batch()
    logits = m.logits(params, batch)
    cache = m.init_cache(B, S)
    outs = []
    for i in range(S):
        lg, cache = m.decode_step(params, cache, batch["tokens"][:, i:i+1], i)
        outs.append(lg)
    dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(logits), atol=2e-4)


@pytest.mark.slow
def test_loss_chunking_matches_full():
    cfg = FAMILY_CFGS[0]
    import dataclasses
    cfg_c = dataclasses.replace(cfg, loss_chunk=4)
    m1, m2 = build_model(cfg), build_model(cfg_c)
    params = m1.init(jax.random.PRNGKey(0))
    l1, _ = m1.loss(params, _batch())
    l2, _ = m2.loss(params, _batch())
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)


@pytest.mark.slow
def test_encdec_decode_matches_forward():
    """seamless-family: decoder decode w/ self-attn cache + cross-attn over
    encoder memory must match the full forward."""
    cfg = ModelConfig(name="ed", arch_type="audio", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=4, d_ff=128, vocab=V,
                      n_enc_layers=2, cut_layer=1)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.arange(B * 12).reshape(B, 12) % V,
             "labels": jnp.ones((B, 12), jnp.int32),
             "frames": 0.1 * jnp.ones((B, 8, 64))}
    logits = m.logits(params, batch)
    memory = m.encode(params, batch)
    cache = m.init_cache(B, 12)
    outs = []
    for i in range(12):
        lg, cache = m.decode_step(params, cache, batch["tokens"][:, i:i+1], i,
                                  memory=memory)
        outs.append(lg)
    dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(logits), atol=2e-4)


def test_vlm_decode_after_patch_prefix():
    """internvl2-family: token decode continuing past an image-patch prefix
    processed by the forward path produces finite logits of the right shape."""
    cfg = ModelConfig(name="vv", arch_type="vlm", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab=V,
                      n_prefix_tokens=4, cut_layer=1)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    cache = m.init_cache(B, 16)
    # feed patch embeddings through decode steps as pseudo-tokens is not the
    # serving path; instead decode plain tokens (image handled at prefill in
    # serving) — check cache decode works for the vlm plan
    logits, cache = m.decode_step(params, cache, jnp.zeros((B, 1), jnp.int32), 0)
    assert logits.shape == (B, 1, V)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))


# ---------------------------------------------------------------------------
# the paper's CNNs: the patch filter gradient of a narrow conv
# ---------------------------------------------------------------------------

# (name, R, H = W, k, C_in, C_out, padding, takes the patch filter gradient):
# the five conv layers of the MNIST and CIFAR-10 CNNs at their cells' R
CONV_LAYERS = [("mnist.conv1", 4, 28, 5, 1, 2, 2, True),
               ("mnist.conv2", 4, 14, 5, 2, 4, 2, False),
               ("cifar10.conv1", 5, 32, 3, 3, 32, 1, False),
               ("cifar10.conv2", 5, 16, 3, 32, 64, 1, False),
               ("cifar10.conv3", 5, 8, 3, 64, 128, 1, False)]


@pytest.mark.parametrize("vmapped", [False, True], ids=["one", "clusters"])
@pytest.mark.parametrize("layer", CONV_LAYERS, ids=lambda l: l[0])
def test_patch_filter_grad_matches_conv_vjp(layer, vmapped):
    """The patch path's filter gradient equals the VJP of the plain
    ``conv_general_dilated`` to float32 tolerance; its forward and input
    gradient are the plain conv's, bit for bit; the shape rule picks the
    layers it says it picks, and ``_conv`` follows it."""
    from repro.models import cnn

    _, r, h, k, c_in, c_out, pad, takes = layer
    assert cnn.takes_patch_filter_grad(k, c_in, c_out) is takes
    kx, kw, kd = jax.random.split(jax.random.PRNGKey(0), 3)
    lead = (r,) if vmapped else ()
    x = jax.random.normal(kx, lead + (2, h, h, c_in))
    w = jax.random.normal(kw, lead + (k, k, c_in, c_out)) / (k * np.sqrt(c_in))
    dy = jax.random.normal(kd, lead + (2, h, h, c_out))

    def plain(x, w):
        return jax.lax.conv_general_dilated(
            x, w, (1, 1), [(pad, pad), (pad, pad)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def fwd_bwd(conv):
        def one(x, w, dy):
            y, vjp = jax.vjp(conv, x, w)
            return (y,) + vjp(dy)
        return jax.jit(jax.vmap(one) if vmapped else one)(x, w, dy)

    y0, dx0, dw0 = map(np.asarray, fwd_bwd(plain))
    y1, dx1, dw1 = map(np.asarray, fwd_bwd(
        lambda x, w: cnn._conv_patch_dw(x, w, pad)))
    np.testing.assert_array_equal(y1, y0)
    np.testing.assert_array_equal(dx1, dx0)
    np.testing.assert_allclose(dw1, dw0, rtol=1e-5,
                               atol=1e-5 * np.abs(dw0).max())
    # _conv takes the patch path exactly where the rule says
    b = jnp.zeros(lead + (c_out,))
    conv = lambda x, w, b: cnn._conv({"w": w, "b": b}, x, pad)
    jaxpr = str(jax.make_jaxpr(jax.vmap(conv) if vmapped else conv)(x, w, b))
    assert ("custom_vjp_call" in jaxpr) is takes


def test_patch_filter_grad_layers_counts_the_rule():
    from repro.models import cnn
    assert cnn.patch_filter_grad_layers(cnn.MNIST_CNN) == 1
    assert cnn.patch_filter_grad_layers(cnn.CIFAR_CNN) == 0
