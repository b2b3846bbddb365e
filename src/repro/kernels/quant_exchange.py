"""Fused quantize->dequantize Pallas TPU kernel for the cut-layer exchange.

The SL wire cost is dominated by the two per-batch cut-layer messages
(activations up, cut gradients down — Table I's 2*E*B*d_c floats per client
turn).  This kernel models the compressed wire: per-row (per-sample)
symmetric quantization to int8 or fp8-e4m3 with one f32 scale per row,
immediately dequantized — the AP-side program consumes exactly the message a
real receiver would reconstruct, and the byte accounting charges
``1 byte/element + 4 bytes/row`` instead of 4 bytes/element.

Two variants share the row-block arithmetic:

  * :func:`quant_dequant` — one pass, grid over row blocks, emits the
    dequantized message (N, D) and the per-row scales (N,).
  * :func:`quant_dequant_stats` — a two-phase grid ``(2, nb)`` that
    additionally fuses the AP-observable anomaly statistics of the
    *dequantized* message (``core.split.message_stats``: dispersion +
    support residual), so anomaly-scoring selection policies pay nothing
    extra for them under quantization.  Phase 0 quantizes and accumulates
    the column sums (the batch mean); phase 1 re-reads the dequantized
    blocks and accumulates the mean-relative distances and support norms
    in SMEM scalars — the ``tamper_check`` accumulator pattern, one level
    up.

Layout: x (N, D) f32; row blocks follow ``kernels.row_block`` (all N rows,
or a multiple of 8 rows, with the whole feature width).  The per-row scales
leave the kernel as an (N, 1) column and the statistics' four raw sums as a
(1, 4) SMEM block; the wrappers reshape the scales to (N,) and finish the
two statistics.  Rows past N in a ragged last block are masked out of every
sum (their outputs are never written), so the statistics' row count stays N.
Validated in interpret mode on CPU against the ``ref.py`` oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import row_block, row_mask

INT8 = "int8"
FP8_E4M3 = "fp8_e4m3"
QUANT_FORMATS = (INT8, FP8_E4M3)

#: symmetric clip range per format (int8: +-127; fp8-e4m3: +-448)
QMAX = {INT8: 127.0, FP8_E4M3: 448.0}

_EPS = 1e-12


def fp8_supported() -> bool:
    """fp8-e4m3 needs a jax/ml_dtypes build exposing ``float8_e4m3fn``."""
    return hasattr(jnp, "float8_e4m3fn")


def check_format(fmt: str) -> None:
    if fmt not in QUANT_FORMATS:
        raise ValueError(f"quant format {fmt!r} must be one of {QUANT_FORMATS}")
    if fmt == FP8_E4M3 and not fp8_supported():
        raise NotImplementedError(
            "fp8_e4m3 quantization needs a jax build with jnp.float8_e4m3fn; "
            "use quant='int8' on this backend")


def _qdq_block(a: jnp.ndarray, fmt: str):
    """Per-row symmetric quantize->dequantize of one (rows, D) f32 block.
    Returns (dequantized block, per-row scales (rows, 1)).  The round trip
    through the narrow dtype is explicit, so the dequantized values are
    exactly what a receiver reconstructs from the wire bytes."""
    qmax = jnp.float32(QMAX[fmt])
    amax = jnp.max(jnp.abs(a), axis=1, keepdims=True)
    s = jnp.maximum(amax, jnp.float32(_EPS)) / qmax
    if fmt == INT8:
        q = jnp.clip(jnp.round(a / s), -qmax, qmax).astype(jnp.int8)
    else:
        q = jnp.clip(a / s, -qmax, qmax).astype(jnp.float8_e4m3fn)
    return q.astype(jnp.float32) * s, s


def _quant_kernel(x_ref, deq_ref, scale_ref, *, fmt):
    deq_ref[...], scale_ref[...] = _qdq_block(x_ref[...].astype(jnp.float32),
                                              fmt)


def _row_specs(block_n: int, d: int, index_map):
    """BlockSpecs of the (N, D) message and the (N, 1) scale column."""
    return (pl.BlockSpec((block_n, d), index_map),
            pl.BlockSpec((block_n, 1), index_map))


def quant_dequant(x: jnp.ndarray, fmt: str, *, block_n: int = 256,
                  interpret: bool = False):
    """x: (N, D) -> (dequantized (N, D) f32, scales (N,) f32)."""
    check_format(fmt)
    n, d = x.shape
    block_n = row_block(n, block_n)
    x_spec, s_spec = _row_specs(block_n, d, lambda i: (i, 0))
    deq, scale = pl.pallas_call(
        functools.partial(_quant_kernel, fmt=fmt),
        grid=(pl.cdiv(n, block_n),),
        in_specs=[x_spec],
        out_specs=[x_spec, s_spec],
        out_shape=[jax.ShapeDtypeStruct((n, d), jnp.float32),
                   jax.ShapeDtypeStruct((n, 1), jnp.float32)],
        interpret=interpret,
    )(x)
    return deq, scale.reshape(n)


def _quant_stats_kernel(x_ref, deq_ref, scale_ref, sums_ref, colsum_scr,
                        acc_scr, *, fmt, block_n, n_rows):
    p = pl.program_id(0)          # phase: 0 quantize+mean, 1 stats
    i = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when((p == 0) & (i == 0))
    def _init():
        colsum_scr[...] = jnp.zeros_like(colsum_scr)
        for k in range(3):
            acc_scr[k] = jnp.float32(0.0)

    deq, scale = _qdq_block(x_ref[...].astype(jnp.float32), fmt)
    deq_ref[...] = deq
    scale_ref[...] = scale
    keep = row_mask(i, block_n, n_rows) if n_rows % block_n else None
    if keep is not None:
        deq = jnp.where(keep, deq, jnp.float32(0.0))

    @pl.when(p == 0)
    def _accumulate_mean():
        colsum_scr[...] = colsum_scr[...] + jnp.sum(deq, axis=0, keepdims=True)

    nt = jnp.float32(n_rows)

    @pl.when(p == 1)
    def _accumulate_stats():
        dev = deq - colsum_scr[...] / nt
        dist = jnp.sqrt(jnp.sum(dev * dev, axis=1, keepdims=True))
        if keep is not None:
            dist = jnp.where(keep, dist, jnp.float32(0.0))
        acc_scr[0] = acc_scr[0] + jnp.sum(dist)
        acc_scr[1] = acc_scr[1] + jnp.sum(jnp.minimum(deq, jnp.float32(0.0)) ** 2)
        acc_scr[2] = acc_scr[2] + jnp.sum(deq * deq)

    @pl.when((p == 1) & (i == nb - 1))
    def _finish():
        mu = colsum_scr[...] / nt
        for k in range(3):
            sums_ref[0, k] = acc_scr[k]
        sums_ref[0, 3] = jnp.sum(mu * mu)


def quant_dequant_stats(x: jnp.ndarray, fmt: str, *, block_n: int = 256,
                        interpret: bool = False):
    """x: (N, D) -> (dequantized (N, D) f32, scales (N,) f32, stats (2,) f32)
    where stats == ``core.split.message_stats`` of the dequantized message."""
    check_format(fmt)
    n, d = x.shape
    block_n = row_block(n, block_n)
    x_spec, s_spec = _row_specs(block_n, d, lambda p, i: (i, 0))
    deq, scale, sums = pl.pallas_call(
        functools.partial(_quant_stats_kernel, fmt=fmt, block_n=block_n,
                          n_rows=n),
        grid=(2, pl.cdiv(n, block_n)),
        in_specs=[x_spec],
        out_specs=[x_spec, s_spec, pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_shape=[jax.ShapeDtypeStruct((n, d), jnp.float32),
                   jax.ShapeDtypeStruct((n, 1), jnp.float32),
                   jax.ShapeDtypeStruct((1, 4), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32),
                        pltpu.SMEM((3,), jnp.float32)],
        interpret=interpret,
    )(x)
    # sums = [sum_i ||deq_i - mu||, ||min(deq, 0)||^2, ||deq||^2, ||mu||^2]
    sums = sums[0]
    eps = jnp.float32(_EPS)
    mu_norm = jnp.maximum(jnp.sqrt(sums[3]), eps)
    dispersion = (sums[0] / jnp.float32(n)) / mu_norm
    support = jnp.sqrt(sums[1]) / jnp.maximum(jnp.sqrt(sums[2]), eps)
    return deq, scale.reshape(n), jnp.stack([dispersion, support])
