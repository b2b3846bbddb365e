"""Fused softmax-cross-entropy Pallas TPU kernel.

This is the compute hot-spot of the paper's selection mechanism: every
global round the AP evaluates the validation loss of all R clusters over the
shared dataset D_o — at LLM scale that is (R x D_o x seq) tokens through a
(d_model x vocab) head.  The fusion computes

    loss[t] = logsumexp_v(h[t] @ W[:, v]) - h[t] @ W[:, label[t]]

by walking vocab panels as the minor sequential grid dimension with a
running (m, l, picked) state in VMEM scratch — the (T x V) logits matrix is
never materialised in HBM (at qwen-scale vocab 152k that saves ~300 GB per
validation pass over the naive path).

Layout: hidden (T, D) f32/bf16, weights (D, V), labels (T,) int32.
Output: per-token loss (T,) f32.  Blocks: (block_t x D) x (D x block_v).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _xent_kernel(h_ref, w_ref, label_ref, o_ref, m_scr, l_scr, pick_scr, *,
                 block_t: int, block_v: int):
    vj = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(vj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        pick_scr[...] = jnp.zeros_like(pick_scr)

    h = h_ref[...].astype(jnp.float32)                       # (bt, D)
    w = w_ref[...].astype(jnp.float32)                       # (D, bv)
    logits = jax.lax.dot_general(h, w, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (bt, bv)
    labels = label_ref[...]                                  # (bt,)
    vocab_ids = vj * block_v + jax.lax.broadcasted_iota(jnp.int32, (block_t, block_v), 1)
    hit = vocab_ids == labels[:, None]
    pick_scr[...] = pick_scr[...] + jnp.sum(jnp.where(hit, logits, 0.0), axis=1)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1))
    l_scr[...] = l_scr[...] * jnp.exp(m_prev - m_new) + jnp.sum(
        jnp.exp(logits - m_new[:, None]), axis=1)
    m_scr[...] = m_new

    @pl.when(vj == nv - 1)
    def _finish():
        lse = m_scr[...] + jnp.log(jnp.maximum(l_scr[...], 1e-30))
        o_ref[...] = (lse - pick_scr[...]).astype(o_ref.dtype)


def fused_xent(hidden: jnp.ndarray, weights: jnp.ndarray, labels: jnp.ndarray, *,
               block_t: int = 256, block_v: int = 512,
               interpret: bool = False) -> jnp.ndarray:
    """hidden (T, D); weights (D, V); labels (T,) -> per-token loss (T,)."""
    t, d = hidden.shape
    _, v = weights.shape
    block_t = min(block_t, t)
    block_v = min(block_v, v)
    assert t % block_t == 0 and v % block_v == 0
    grid = (t // block_t, v // block_v)
    kernel = functools.partial(_xent_kernel, block_t=block_t, block_v=block_v)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, block_v), lambda i, j: (0, j)),
            pl.BlockSpec((block_t,), lambda i, j: (i,)),
        ],
        out_specs=pl.BlockSpec((block_t,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((t,), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((block_t,), jnp.float32),
            pltpu.VMEM((block_t,), jnp.float32),
            pltpu.VMEM((block_t,), jnp.float32),
        ],
        interpret=interpret,
        name="fused_xent",
    )(hidden, weights, labels)
