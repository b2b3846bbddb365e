"""Jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to True on the CPU backend (tests run the kernels in
the Pallas interpreter against the ref.py oracles) and to False on TPU, where
they compile to Mosaic.  Any other backend raises: the kernels are written
for TPU, and silently interpreting them elsewhere would hide the device.
"""
from __future__ import annotations

from functools import lru_cache, partial
from typing import Optional

import jax
import jax.numpy as jnp

from . import flash_attention as _fa
from . import fused_xent as _fx
from . import tamper_check as _tc


def _default_interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"the Pallas kernels compile for TPU and are interpreted on CPU; "
            f"backend {backend!r} is neither (pass interpret= explicitly)")
    return backend == "cpu"


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                                   "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None):
    """Multi-head attention. q: (B, Sq, H, D); k, v: (B, Sk, Hkv, D).
    Returns (B, Sq, H, D)."""
    interpret = _default_interpret() if interpret is None else interpret
    b, sq, h, d = q.shape
    _, sk, hkv, _ = k.shape
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, sk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, sk, d)
    of = _fa.flash_attention(qf, kf, vf, causal=causal, window=window,
                             block_q=block_q, block_k=block_k,
                             interpret=interpret)
    return of.reshape(b, h, sq, d).transpose(0, 2, 1, 3)


@partial(jax.jit, static_argnames=("block_t", "block_v", "interpret"))
def fused_cross_entropy(hidden, weights, labels, *, block_t: int = 256,
                        block_v: int = 512, interpret: Optional[bool] = None):
    """Mean fused softmax-xent.  hidden (..., D); labels (...,)."""
    interpret = _default_interpret() if interpret is None else interpret
    d = hidden.shape[-1]
    h2 = hidden.reshape(-1, d)
    l2 = labels.reshape(-1)
    per_tok = _fx.fused_xent(h2, weights, l2, block_t=block_t, block_v=block_v,
                             interpret=interpret)
    return jnp.mean(per_tok)


@partial(jax.jit, static_argnames=("window", "block_k", "interpret"))
def decode_attention(q, k, v, index, *, window: int = 0, block_k: int = 512,
                     interpret: Optional[bool] = None):
    """Single-token decode attention. q: (B, 1, H, D); k, v: (B, S, Hkv, D);
    index: scalar position of the new token.  Returns (B, 1, H, D)."""
    from . import decode_attention as _da
    interpret = _default_interpret() if interpret is None else interpret
    b, _, h, d = q.shape
    _, s, hkv, _ = k.shape
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, 1, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    of = _da.decode_attention(qf, kf, vf, index, window=window,
                              block_k=block_k, interpret=interpret)
    return of.reshape(b, h, 1, d).transpose(0, 2, 1, 3)


@partial(jax.jit, static_argnames=("n_heads", "interpret"))
def slstm_scan(pre, r, *, n_heads: int, interpret: Optional[bool] = None):
    """Fused sLSTM time scan. pre: (T, B, 4d); r: (H, dh, 4dh) -> (T, B, d)."""
    from . import slstm_scan as _ss
    interpret = _default_interpret() if interpret is None else interpret
    return _ss.slstm_scan(pre, r, n_heads=n_heads, interpret=interpret)


def largest_divisor(n: int, cap: int) -> int:
    """Largest divisor of n at most ``cap`` — the selection subsystem's
    shard-count clamp (``repro.selection.effective_shards``)."""
    cap = max(1, min(cap, n))
    while n % cap:
        cap -= 1
    return cap


@partial(jax.jit, static_argnames=("fmt", "block_n", "interpret"))
def quant_roundtrip(x, fmt: str, *, block_n: int = 256,
                    interpret: Optional[bool] = None):
    """Per-row symmetric quantize->dequantize of a (N, D) message through
    the ``quant_exchange`` kernel.  Returns (dequantized (N, D) f32,
    per-row scales (N,) f32) — the message a receiver reconstructs from
    ``1 byte/element + 4 bytes/row`` on the wire."""
    from . import quant_exchange as _qx
    interpret = _default_interpret() if interpret is None else interpret
    return _qx.quant_dequant(x, fmt, block_n=block_n, interpret=interpret)


@partial(jax.jit, static_argnames=("fmt", "block_n", "interpret"))
def quant_roundtrip_stats(x, fmt: str, *, block_n: int = 256,
                          interpret: Optional[bool] = None):
    """:func:`quant_roundtrip` fused with the AP-observable message
    statistics of the *dequantized* message (``core.split.message_stats``:
    dispersion + support residual) — anomaly-scoring selection policies pay
    nothing extra under quantization.  Returns (deq, scales, stats (2,))."""
    from . import quant_exchange as _qx
    interpret = _default_interpret() if interpret is None else interpret
    return _qx.quant_dequant_stats(x, fmt, block_n=block_n,
                                   interpret=interpret)


@lru_cache(maxsize=None)
def _quant_exchange_fn(fmt: str):
    """Straight-through both-direction wire model for fused SPMD train steps
    (the launch layer): the forward quantizes the uplink activation message,
    the backward quantizes the downlink cut-gradient cotangent — one
    ``value_and_grad`` over the composed split model then sees exactly the
    two messages a real AP/client pair would exchange."""

    def _qdq(x):
        flat = x.reshape(x.shape[0], -1).astype(jnp.float32)
        deq, _ = quant_roundtrip(flat, fmt)
        return deq.reshape(x.shape).astype(x.dtype)

    @jax.custom_vjp
    def exchange(x):
        return _qdq(x)

    def fwd(x):
        return _qdq(x), None

    def bwd(_, g):
        return (_qdq(g),)

    exchange.defvjp(fwd, bwd)
    return exchange


def quant_cut_exchange(x, fmt: Optional[str]):
    """Apply the quantized cut-layer wire to an activation tensor (leading
    batch axis, any trailing shape).  ``fmt=None`` is the f32 identity."""
    if fmt is None:
        return x
    from . import quant_exchange as _qx
    _qx.check_format(fmt)
    return _quant_exchange_fn(fmt)(x)


@partial(jax.jit, static_argnames=("block_n", "interpret"))
def tamper_distance(ref, recv, *, block_n: int = 256,
                    interpret: Optional[bool] = None):
    """Relative L2 distance ||ref-recv|| / ||ref|| between activation sets.
    ref/recv: (..., D) — flattened to (N, D).  The fused selection cascade's
    verify stage (``repro.selection``) maps this over the R candidate
    handoffs inside the compiled round program."""
    interpret = _default_interpret() if interpret is None else interpret
    d = ref.shape[-1]
    a = ref.reshape(-1, d)
    b = recv.reshape(-1, d)
    sums = _tc.tamper_check_sums(a, b, block_n=block_n, interpret=interpret)
    return jnp.sqrt(sums[0]) / jnp.maximum(jnp.sqrt(sums[1]), 1e-12)
