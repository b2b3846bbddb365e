"""Tamper-check Pallas TPU kernel.

The Section III-C defence compares the cut-layer activations transmitted by
the next-round first clients against the validation-time reference — at LLM
scale that is R x (D_o x seq x d_model) element-wise distances per round.
The kernel streams both activation matrices through VMEM in (block_n x D)
panels and accumulates the squared-L2 distance and the reference squared
norm in SMEM scalars, emitting the single (relative-distance numerator,
denominator) pair — one pass over HBM, no intermediate difference tensor.

Layout: ref, recv (N, D); output (2,) f32 = [sum |a-b|^2, sum |a|^2], held
in SMEM as a (1, 2) block so that a vmapped call keeps legal block shapes.
Row blocks are a multiple of 8 rows (or all N rows) with the whole feature
width; when N is not a multiple of the block, the rows past N in the last
block are masked to zero, which adds nothing to either sum.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import row_block, row_mask


def _tamper_kernel(ref_ref, recv_ref, o_ref, acc_scr, *, block_n, n_rows):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_scr[0] = jnp.float32(0.0)
        acc_scr[1] = jnp.float32(0.0)

    a = ref_ref[...].astype(jnp.float32)
    b = recv_ref[...].astype(jnp.float32)
    if n_rows % block_n:
        keep = row_mask(i, block_n, n_rows)
        a = jnp.where(keep, a, jnp.float32(0.0))
        b = jnp.where(keep, b, jnp.float32(0.0))
    d = a - b
    acc_scr[0] = acc_scr[0] + jnp.sum(d * d)
    acc_scr[1] = acc_scr[1] + jnp.sum(a * a)

    @pl.when(i == pl.num_programs(0) - 1)
    def _finish():
        o_ref[0, 0] = acc_scr[0]
        o_ref[0, 1] = acc_scr[1]


def tamper_check_sums(ref: jnp.ndarray, recv: jnp.ndarray, *,
                      block_n: int = 256, interpret: bool = False) -> jnp.ndarray:
    """ref, recv: (N, D) -> (2,) = [||ref - recv||^2, ||ref||^2]."""
    n, d = ref.shape
    block_n = row_block(n, block_n)
    return pl.pallas_call(
        functools.partial(_tamper_kernel, block_n=block_n, n_rows=n),
        grid=(pl.cdiv(n, block_n),),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((1, 2), jnp.float32),
        scratch_shapes=[pltpu.SMEM((2,), jnp.float32)],
        interpret=interpret,
    )(ref, recv)[0]
