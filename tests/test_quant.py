"""Quantized cut-layer exchange: kernel vs oracle, protocol equivalence and
the security property.

Three layers of contract:

  * kernel — ``kernels.quant_exchange`` matches the ``ref.py`` pure-jnp
    oracle bit-for-bit in interpret mode, the round-trip error is within the
    per-row quantization step, and the fused stats equal
    ``message_stats_reference`` of the dequantized message.
  * accounting — ``CommMeter`` byte totals follow ``message_bytes`` exactly
    (1 byte/element + 4 bytes/row vs 4 bytes/element), float counts (the
    Table I quantities) are format-independent, and the engines stay
    bit-identical under quantization.
  * security — selection honesty under the paper's three attacks is
    unchanged by the quantized wire (the ISSUE's headline property).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ACTIVATION, GRADIENT, LABEL_FLIP, Attack, CommConfig,
                        ProtocolConfig, message_bytes, resolve_quant,
                        run_pigeon, run_splitfed)
from repro.kernels import ops, ref
from repro.kernels.quant_exchange import (FP8_E4M3, INT8, QMAX,
                                          check_format, fp8_supported,
                                          quant_dequant, quant_dequant_stats)

FORMATS = [INT8,
           pytest.param(FP8_E4M3,
                        marks=pytest.mark.skipif(not fp8_supported(),
                                                 reason="no jnp.float8_e4m3fn"))]


# ---------------------------------------------------------------------------
# kernel vs oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt", FORMATS)
@pytest.mark.parametrize("n,d", [(64, 32), (256, 160),
                                 pytest.param(512, 33, marks=pytest.mark.slow),
                                 (3000, 256),    # D_o = 3000: ragged blocks
                                 (1001, 64)])    # N not a multiple of 8
def test_quant_roundtrip_matches_reference(fmt, n, d):
    """Both kernel variants against the oracle; with block_n=64 the larger
    shapes end in a masked partial block, which must leave the statistics'
    row count at N."""
    x = jax.random.normal(jax.random.PRNGKey(0), (n, d)) * 3.0 + 0.5
    deq_ref, scales_ref = ref.quant_roundtrip_reference(x, fmt)
    deq, scales = quant_dequant(x, fmt, block_n=64, interpret=True)
    deq_s, scales_s, stats = quant_dequant_stats(x, fmt, block_n=64,
                                                 interpret=True)
    # same codebook, same scales — up to one float32 ulp of non-associativity
    # between the interpret-mode and pure-jnp multiply orders
    for got_deq, got_scales in ((deq, scales), (deq_s, scales_s)):
        np.testing.assert_allclose(np.asarray(got_deq), np.asarray(deq_ref),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(got_scales),
                                   np.asarray(scales_ref), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(stats),
                               np.asarray(ref.message_stats_reference(deq_ref)),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("fmt", FORMATS)
def test_quant_roundtrip_error_bound(fmt):
    """Per-row symmetric quantization error: every element is within one
    quantization step of the original (int8: scale/2 from rounding; fp8:
    relative precision of a 3-bit mantissa near the row max)."""
    x = jax.random.normal(jax.random.PRNGKey(1), (128, 96)) * 5.0
    deq, scales = ops.quant_roundtrip(x, fmt, interpret=True)
    err = np.abs(np.asarray(deq) - np.asarray(x))
    step = np.asarray(scales)[:, None]
    if fmt == INT8:
        bound = 0.5 * step + 1e-7
    else:
        # e4m3: relative error <= 2^-4 of the magnitude, plus the subnormal
        # floor at scale * 2^-9
        bound = np.abs(np.asarray(x)) * 2.0 ** -4 + step * 2.0 ** -9 + 1e-7
    assert (err <= bound).all(), float(np.max(err - bound))
    # the row scale is exactly rowmax/qmax
    np.testing.assert_allclose(
        np.asarray(scales),
        np.max(np.abs(np.asarray(x)), axis=1) / QMAX[fmt], rtol=1e-6)


@pytest.mark.parametrize("fmt", FORMATS)
def test_quant_stats_fusion_matches_reference(fmt):
    """The fused two-phase kernel's stats equal message_stats_reference of
    its own dequantized output, and its deq/scales equal the plain kernel."""
    x = jax.random.normal(jax.random.PRNGKey(2), (128, 48)) + 0.5
    deq, scales, stats = quant_dequant_stats(x, fmt, block_n=32,
                                             interpret=True)
    deq_ref, scales_ref = ref.quant_roundtrip_reference(x, fmt)
    np.testing.assert_allclose(np.asarray(deq), np.asarray(deq_ref),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(scales), np.asarray(scales_ref),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(stats),
                               np.asarray(ref.message_stats_reference(deq_ref)),
                               rtol=1e-5, atol=1e-6)


def test_quant_roundtrip_is_idempotent():
    """QDQ(QDQ(x)) == QDQ(x): dequantized values are exact codebook points."""
    x = jax.random.normal(jax.random.PRNGKey(3), (64, 32))
    once, _ = ops.quant_roundtrip(x, INT8, interpret=True)
    twice, _ = ops.quant_roundtrip(once, INT8, interpret=True)
    np.testing.assert_allclose(np.asarray(twice), np.asarray(once),
                               rtol=1e-6, atol=1e-7)


def test_quant_cut_exchange_straight_through_grad():
    """The launch-layer wire op: forward quantizes the uplink, backward
    quantizes the downlink cotangent (not a pass-through of it)."""
    x = jax.random.normal(jax.random.PRNGKey(4), (8, 16))
    w = jax.random.normal(jax.random.PRNGKey(5), (16,))

    def loss(x):
        return jnp.sum(ops.quant_cut_exchange(x, INT8) * w)

    g = jax.grad(loss)(x)
    cot = jnp.broadcast_to(w, x.shape)
    g_ref, _ = ref.quant_roundtrip_reference(cot, INT8)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(g_ref))
    # fmt=None is the exact f32 identity, both directions
    assert ops.quant_cut_exchange(x, None) is x
    np.testing.assert_array_equal(
        np.asarray(jax.grad(lambda x: jnp.sum(ops.quant_cut_exchange(x, None)
                                              * w))(x)),
        np.asarray(cot))


def test_quant_format_validation():
    with pytest.raises(ValueError):
        check_format("int4")
    with pytest.raises(ValueError):
        CommConfig(quant="int4")
    assert resolve_quant("fp8") == FP8_E4M3
    assert resolve_quant(None) is None
    assert CommConfig(quant="e4m3").quant == FP8_E4M3


# ---------------------------------------------------------------------------
# accounting + engine equivalence
# ---------------------------------------------------------------------------

def test_message_bytes_accounting():
    # f32: 4 bytes/element; quantized: 1 byte/element + one f32 scale per row
    assert message_bytes(None, 16, 256) == 16 * 256 * 4
    assert message_bytes(INT8, 16, 256) == 16 * 256 + 16 * 4
    ratio = message_bytes(None, 16, 256) / message_bytes(INT8, 16, 256)
    assert ratio == pytest.approx(4 * 256 / 260)


def _comm_totals(h):
    keys = ("activation_bytes", "gradient_bytes", "param_bytes",
            "validation_bytes", "activation_floats", "gradient_floats",
            "param_floats", "validation_floats", "client_passes")
    return {k: sum(r["comm"][k] for r in h.rounds) for k in keys}


def test_pigeon_quant_bytes_and_float_counts(tiny_task, tiny_pcfg):
    """int8 cuts exchange bytes by 4*d_c/(d_c+4) while the Table I float
    counts and the defense-critical param/validation traffic stay put."""
    data, module = tiny_task
    h32 = run_pigeon(module, data, tiny_pcfg)
    h8 = run_pigeon(module, data, tiny_pcfg, quant="int8")
    t32, t8 = _comm_totals(h32), _comm_totals(h8)
    for k in ("activation_floats", "gradient_floats", "param_floats",
              "validation_floats", "client_passes", "param_bytes",
              "validation_bytes"):
        assert t32[k] == t8[k], k
    d_c = 32                                    # MNIST_CNN fc_sizes=(32,)
    expect = 4 * d_c / (d_c + 4)
    assert t32["activation_bytes"] / t8["activation_bytes"] == pytest.approx(expect)
    assert t32["gradient_bytes"] / t8["gradient_bytes"] == pytest.approx(expect)


@pytest.mark.parametrize("placement", ["vmap",
                                       pytest.param("sharded",
                                                    marks=pytest.mark.slow)])
def test_pigeon_engine_equivalence_under_quant(tiny_task, tiny_pcfg, placement):
    """Sequential and batched engines agree on trajectory and report
    bit-identical CommMeter records under the quantized wire."""
    data, module = tiny_task
    h_seq = run_pigeon(module, data, tiny_pcfg, malicious={1},
                       attack=Attack(LABEL_FLIP), quant="int8")
    h_bat = run_pigeon(module, data, tiny_pcfg, malicious={1},
                       attack=Attack(LABEL_FLIP), engine="batched",
                       placement=placement, quant="int8")
    for rs, rb in zip(h_seq.rounds, h_bat.rounds):
        assert rs["selected"] == rb["selected"]
        assert rs["selected_honest"] == rb["selected_honest"]
        np.testing.assert_allclose(rs["val_losses"], rb["val_losses"],
                                   rtol=2e-5, atol=1e-6)
        assert rs["comm"] == rb["comm"]


@pytest.mark.parametrize("quant", [None, "int8"])
def test_splitfed_comm_identical_across_engines(tiny_task, tiny_pcfg, quant):
    """run_splitfed now meters communication; the analytic per-round record
    is bit-identical between the sequential and batched engines and is
    non-zero (the pre-fix behaviour was no ``comm`` record at all)."""
    data, module = tiny_task
    h_seq = run_splitfed(module, data, tiny_pcfg, quant=quant)
    h_bat = run_splitfed(module, data, tiny_pcfg, engine="batched",
                         quant=quant)
    for rs, rb in zip(h_seq.rounds, h_bat.rounds):
        assert rs["comm"] == rb["comm"]
        assert rs["comm"]["activation_bytes"] > 0
        assert rs["comm"]["param_bytes"] > 0


# ---------------------------------------------------------------------------
# the security property
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("attack", [Attack(LABEL_FLIP), Attack(ACTIVATION),
                                    Attack(GRADIENT)], ids=lambda a: a.kind)
def test_selection_honesty_unchanged_under_quant(tiny_task, attack):
    """The paper's three attacks: the selected-cluster sequence — hence the
    honesty of every selection — is identical with and without int8
    quantization of the cut-layer wire."""
    data, module = tiny_task
    pcfg = ProtocolConfig(M=4, N=1, T=3, E=2, B=16, lr=0.05, seed=0)
    h32 = run_pigeon(module, data, pcfg, malicious={1}, attack=attack,
                     engine="batched")
    h8 = run_pigeon(module, data, pcfg, malicious={1}, attack=attack,
                    engine="batched", quant="int8")
    assert [r["selected"] for r in h32.rounds] == \
           [r["selected"] for r in h8.rounds]
    assert [r["selected_honest"] for r in h32.rounds] == \
           [r["selected_honest"] for r in h8.rounds]
