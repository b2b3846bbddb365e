"""Flash-decoding Pallas TPU kernel: one new token against a long KV cache.

The decode_32k / long_500k serve rows are memory-bound on the KV-cache sweep
(and collective-bound when GSPMD all-gathers sharded caches).  This kernel
streams the cache through VMEM in (block_k x d) panels with a running
softmax carry, so per-step HBM traffic is exactly one cache read and the
(1 x S) score row never materialises.  With the cache sequence-sharded
(`--seq-shard-cache` layout) each shard runs this kernel over its local
panel and the partial (out, m, l) triples combine with one tiny psum —
the shard_map flash-decoding schedule.

Layout: q (BH, 1, D); k, v (BHkv, S, D); index = number of valid cache
positions - 1 (causal: attend to k_pos <= index), optional sliding window.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(idx_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                   scale: float, block_k: int, window: int):
    kj = pl.program_id(1)
    nk = pl.num_programs(1)
    index = idx_ref[0]

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    k_pos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    valid = k_pos <= index
    if window > 0:
        valid = valid & (index - k_pos < window)

    q = q_ref[0].astype(jnp.float32)                        # (1, d)
    k = k_ref[0].astype(jnp.float32)                        # (bk, d)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale  # (1, bk)
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(kj == nk - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     index: jnp.ndarray, *, window: int = 0,
                     scale: float | None = None, block_k: int = 512,
                     interpret: bool = False) -> jnp.ndarray:
    """q: (BH, 1, D); k, v: (BHkv, S, D); index: scalar int32.
    Returns (BH, 1, D)."""
    bh, _, d = q.shape
    bhkv, s, _ = k.shape
    assert bh % bhkv == 0
    groups = bh // bhkv
    block_k = min(block_k, s)
    assert s % block_k == 0
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    kernel = functools.partial(_decode_kernel, scale=scale, block_k=block_k,
                               window=window)
    idx = jnp.asarray(index, jnp.int32).reshape(1)
    return pl.pallas_call(
        kernel,
        grid=(bh, s // block_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j: (b // groups, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j: (b // groups, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, 1, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ],
        interpret=interpret,
        name="decode_attention",
    )(idx, q, k, v)
