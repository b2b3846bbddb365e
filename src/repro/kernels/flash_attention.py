"""Flash attention Pallas TPU kernel (causal, GQA, optional sliding window).

TPU adaptation of the classic GPU flash-attention blocking: instead of a
warp-level streaming softmax, the kernel tiles (block_q x d) query panels and
(block_k x d) key/value panels into VMEM and walks the key axis as the
*minor sequential grid dimension*, carrying the running (m, l, acc) softmax
state in VMEM scratch between grid steps.  Block shapes default to
(128, 128) so the q @ k^T and p @ v contractions land on MXU-aligned
(128, head_dim) tiles.  HBM traffic is Q+K+V+O only — the (S x S) score
matrix never leaves VMEM, which removes the dominant memory-roofline term of
the XLA attention path (see EXPERIMENTS.md §Perf).

Layout: q (BH, Sq, D); k, v (BHkv, Sk, D).  GQA is handled in the index
maps: query row b maps to kv row (b // H) * Hkv + (b % H) // (H // Hkv).

Validated against ``ref.mha_reference`` in interpret mode (tests/test_kernels_*).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, block_q: int, block_k: int, seq_k: int,
                  window: int, causal: bool):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask = mask & (q_pos >= k_pos)
    if window > 0:
        mask = mask & (q_pos - k_pos < window)

    # skip fully-masked blocks (still executed — grid steps are sequential —
    # but the vector work is predicated out)
    block_live = jnp.logical_not(causal) | (qi * block_q + block_q - 1 >= kj * block_k)
    if window > 0:
        block_live = block_live & (kj * block_k + block_k - 1 > qi * block_q - window)

    @pl.when(block_live)
    def _step():
        q = q_ref[0].astype(jnp.float32)                    # (bq, d)
        k = k_ref[0].astype(jnp.float32)                    # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(kj == nk - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int = 0,
                    scale: float | None = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    """q: (BH, Sq, D); k, v: (BHkv, Sk, D) with BH % BHkv == 0."""
    bh, sq, d = q.shape
    bhkv, sk, _ = k.shape
    assert bh % bhkv == 0
    groups = bh // bhkv
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    grid = (bh, sq // block_q, sk // block_k)

    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_k=block_k,
        seq_k=sk, window=window, causal=causal)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // groups, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // groups, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            _vmem((block_q,), jnp.float32),
            _vmem((block_q,), jnp.float32),
            _vmem((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)
