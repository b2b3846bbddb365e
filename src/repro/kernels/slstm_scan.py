"""Fused sLSTM time-scan Pallas TPU kernel.

§Perf hillclimb B found the sLSTM layers' dominant HBM traffic to be the
recurrent weight matrix R (and, in training, its gradient accumulator)
streamed from HBM at *every timestep* of the 4096-step scan — ~50% of the
xlstm-1.3b training bytes. The TPU-native fix is structural: keep R and the
(h, c, n, m) state resident in VMEM across the whole time loop and stream
only the per-step pre-activations.

Kernel layout: grid = (T,) sequential; R is tiled into VMEM once via a
constant index_map (Pallas keeps the block resident since the slice never
changes); the running state lives in VMEM scratch. Per-step HBM traffic
drops from (R 16 MB + x_t) to (x_t + h_t) — the K-fold `slstm_unroll`
XLA mitigation approaches this, the kernel *is* the limit case.

Stabilised exponential gating follows xLSTM [arXiv:2405.04517] exactly
(same math as models/xlstm._slstm_step); validated against it in
interpret mode by tests/test_kernels.py::test_slstm_kernel_matches_reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _slstm_kernel(pre_ref, r_ref, o_ref, h_scr, c_scr, n_scr, m_scr, *,
                  n_heads: int, d_head: int):
    t = pl.program_id(0)
    d = n_heads * d_head

    @pl.when(t == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)
        c_scr[...] = jnp.zeros_like(c_scr)
        n_scr[...] = jnp.zeros_like(n_scr)
        m_scr[...] = jnp.full_like(m_scr, -1e30)

    pre = pre_ref[0].astype(jnp.float32)                    # (B, 4d)
    b = pre.shape[0]
    # recurrent contribution: block-diagonal per head.  r_ref: (H, dh, 4dh)
    h_prev = h_scr[...].reshape(b, n_heads, d_head)
    rec = jax.lax.dot_general(
        h_prev.transpose(1, 0, 2), r_ref[...].astype(jnp.float32),
        (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)                 # (H, B, 4dh)
    rec = rec.transpose(1, 0, 2).reshape(b, 4 * d)
    z = pre + rec
    li, lf_raw, zz, oo = jnp.split(z, 4, axis=-1)
    lf = jax.nn.log_sigmoid(lf_raw)
    m_new = jnp.maximum(lf + m_scr[...], li)
    i = jnp.exp(li - m_new)
    f = jnp.exp(lf + m_scr[...] - m_new)
    c = f * c_scr[...] + i * jnp.tanh(zz)
    n = f * n_scr[...] + i
    h = jax.nn.sigmoid(oo) * c / jnp.maximum(n, 1.0)
    c_scr[...] = c
    n_scr[...] = n
    m_scr[...] = m_new
    h_scr[...] = h
    o_ref[0] = h.astype(o_ref.dtype)


def slstm_scan(pre: jnp.ndarray, r: jnp.ndarray, *, n_heads: int,
               interpret: bool = False) -> jnp.ndarray:
    """pre: (T, B, 4d) input pre-activations; r: (H, dh, 4*dh) recurrent
    weights (gates ordered [i, f, z, o] both in ``pre`` columns and in the
    last dim of ``r`` per head).  Returns hidden states (T, B, d)."""
    t, b, d4 = pre.shape
    d = d4 // 4
    d_head = d // n_heads
    kernel = functools.partial(_slstm_kernel, n_heads=n_heads, d_head=d_head)
    return pl.pallas_call(
        kernel,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, b, 4 * d), lambda i: (i, 0, 0)),
            pl.BlockSpec((n_heads, d_head, 4 * d_head), lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, b, d), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((t, b, d), pre.dtype),
        scratch_shapes=[
            pltpu.VMEM((b, d), jnp.float32),   # h
            pltpu.VMEM((b, d), jnp.float32),   # c
            pltpu.VMEM((b, d), jnp.float32),   # n
            pltpu.VMEM((b, d), jnp.float32),   # m
        ],
        interpret=interpret,
        name="slstm_scan",
    )(pre, r)
