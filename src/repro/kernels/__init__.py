"""Pallas TPU kernels (``ops.py`` wrappers, ``ref.py`` pure-jnp oracles).

The row-blocked kernels (``tamper_check``, ``quant_exchange``) share the
tiling rule below: a block is all N rows, or a multiple of 8 rows (the f32
sublane tile) with the whole feature width, and a ragged last block is
masked row by row.
"""
import jax
import jax.numpy as jnp


def row_block(n: int, cap: int) -> int:
    """Rows per block for an (N, D) operand: all N when N <= ``cap``, else
    ``cap`` rounded down to a multiple of 8 (at least 8)."""
    if n <= cap:
        return n
    return max(8, cap - cap % 8)


def row_mask(i, block_n: int, n_rows: int) -> jnp.ndarray:
    """(block_n, 1) bool: which rows of grid block ``i`` lie inside N."""
    rows = i * block_n + jax.lax.broadcasted_iota(jnp.int32, (block_n, 1), 0)
    return rows < n_rows
