"""The paper's exact simulation models (Section V-A).

MNIST:    conv(1->2, 5x5, pad 2) - pool - conv(2->4, 5x5, pad 2) - pool -
          FC 32 (cut layer) - FC 10.
CIFAR-10: conv(3->32, 3x3) - pool - conv(32->64, 3x3) - pool -
          conv(64->128, 3x3) - pool - FC 256 (cut layer) - FC 128 - FC 64 - FC 10.

The cut layer is the first fully-connected layer, exactly as described: the
client-side NN ends at the cut-layer output (d_c = 32 / 256), the AP-side NN
consumes it.  2x2 max-pooling after each conv keeps the FC sizes manageable
(the paper does not spell out pooling; this is the standard choice).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from .blocks import Params, cross_entropy, dense_init


@dataclasses.dataclass(frozen=True)
class CNNConfig:
    name: str
    image_size: int
    in_channels: int
    conv_channels: Tuple[int, ...]
    kernel: int
    padding: int
    fc_sizes: Tuple[int, ...]         # first entry is the cut layer width d_c
    n_classes: int = 10

    @property
    def d_cut(self) -> int:
        return self.fc_sizes[0]

    @property
    def flat_dim(self) -> int:
        s = self.image_size
        for _ in self.conv_channels:
            s = s // 2
        return s * s * self.conv_channels[-1]


MNIST_CNN = CNNConfig(name="mnist_cnn", image_size=28, in_channels=1,
                      conv_channels=(2, 4), kernel=5, padding=2,
                      fc_sizes=(32,))
CIFAR_CNN = CNNConfig(name="cifar_cnn", image_size=32, in_channels=3,
                      conv_channels=(32, 64, 128), kernel=3, padding=1,
                      fc_sizes=(256, 128, 64))


def _conv_init(key, k: int, c_in: int, c_out: int) -> Params:
    w = jax.random.truncated_normal(key, -2, 2, (k, k, c_in, c_out)) / math.sqrt(k * k * c_in)
    return {"w": w.astype(jnp.float32), "b": jnp.zeros((c_out,), jnp.float32)}


_DIMS = ("NHWC", "HWIO", "NHWC")


def _conv_xla(x: jnp.ndarray, w: jnp.ndarray, padding: int) -> jnp.ndarray:
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1),
        padding=[(padding, padding), (padding, padding)],
        dimension_numbers=_DIMS)


def takes_patch_filter_grad(k: int, c_in: int, c_out: int) -> bool:
    """Whether a k x k conv from ``c_in`` to ``c_out`` channels takes its
    filter gradient from flat patch rows (:func:`_patch_filter_grad`).

    XLA's own filter gradient, vmapped over clusters, makes the whole image
    the convolution window and the batch its contraction feature.  On a
    TPU v5e, for MNIST's conv1 (25 x 1 x 2 weights) that took 326 us a
    step against 58 us from flat patch rows; for MNIST's conv2 (200
    weights) and the CIFAR-10 convs (864 and more) XLA's took 31 us and
    69 us against 62 us and 767 us (``benchmarks/conv_filter_grad.py``).
    The flat rows cost one product per weight and pixel on the vector
    unit, so they pay only for a filter of few weights."""
    return k * k * c_in * c_out <= 64


def _patch_filter_grad(x: jnp.ndarray, dy: jnp.ndarray, k: int,
                       padding: int) -> jnp.ndarray:
    """dw[i, j, c, o] = sum over b, h, w of xpad[b, h+i, w+j, c] dy[b, h, w, o]
    for a stride-1 conv, as float32 products summed on the vector unit.

    Each zero-padded image is flattened to rows of pitch wq = W + 2*padding,
    so tap (i, j) of every output pixel (h, w) is flat element
    (h + i)*wq + w + j: one contiguous slice of length Ho*wq per tap, with
    B and the flat pixels on the TPU's sublanes and lanes.  dy is padded
    with zero columns to the same pitch, so the slice's wrapped-around
    columns add nothing."""
    b, _, w, c = x.shape
    _, ho, wo, o = dy.shape
    wq = w + 2 * padding
    n = ho * wq
    # one more zero row keeps the last tap's slice inside the flat image
    xf = jnp.pad(x, ((0, 0), (padding, padding + 1), (padding, padding),
                     (0, 0)))
    xf = xf.transpose(3, 0, 1, 2).reshape(c, b, -1)
    dyf = jnp.pad(dy, ((0, 0), (0, 0), (0, wq - wo), (0, 0)))
    dyf = dyf.transpose(3, 0, 1, 2).reshape(o, b, n)
    taps = jnp.stack([xf[:, :, i * wq + j:i * wq + j + n]
                      for i in range(k) for j in range(k)])
    dw = jnp.sum(taps[:, :, None] * dyf[None, None], axis=(3, 4))
    return dw.reshape(k, k, c, o)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _conv_patch_dw(x: jnp.ndarray, w: jnp.ndarray,
                   padding: int) -> jnp.ndarray:
    """The same convolution as :func:`_conv_xla`, forward and input gradient
    alike; only its filter gradient is :func:`_patch_filter_grad`."""
    return _conv_xla(x, w, padding)


def _conv_patch_dw_fwd(x, w, padding):
    return _conv_xla(x, w, padding), (x, w)


def _conv_patch_dw_bwd(padding, res, dy):
    x, w = res
    _, vjp_x = jax.vjp(lambda x_: _conv_xla(x_, w, padding), x)
    (dx,) = vjp_x(dy)
    with jax.named_scope("conv_filter_grad"):
        dw = _patch_filter_grad(x, dy, w.shape[0], padding)
    return dx, dw


_conv_patch_dw.defvjp(_conv_patch_dw_fwd, _conv_patch_dw_bwd)


def _conv(p: Params, x: jnp.ndarray, padding: int) -> jnp.ndarray:
    k, _, c_in, c_out = p["w"].shape
    conv = (_conv_patch_dw if takes_patch_filter_grad(k, c_in, c_out)
            else _conv_xla)
    return conv(x, p["w"], padding) + p["b"]


def patch_filter_grad_layers(cfg: CNNConfig) -> int:
    """How many of ``cfg``'s conv layers take the patch filter gradient."""
    chans = (cfg.in_channels,) + tuple(cfg.conv_channels)
    return sum(takes_patch_filter_grad(cfg.kernel, c_in, c_out)
               for c_in, c_out in zip(chans, chans[1:]))


def _maxpool2(x: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def cnn_init(key, cfg: CNNConfig) -> Tuple[Params, Params]:
    """Returns (gamma, phi): client-side and AP-side parameters."""
    n_conv = len(cfg.conv_channels)
    keys = jax.random.split(key, n_conv + len(cfg.fc_sizes) + 1)
    convs = []
    c_in = cfg.in_channels
    for i, c_out in enumerate(cfg.conv_channels):
        convs.append(_conv_init(keys[i], cfg.kernel, c_in, c_out))
        c_in = c_out
    cut_fc = {"w": dense_init(keys[n_conv], cfg.flat_dim, cfg.d_cut),
              "b": jnp.zeros((cfg.d_cut,), jnp.float32)}
    gamma = {"convs": tuple(convs), "cut_fc": cut_fc}

    fcs = []
    d_in = cfg.d_cut
    for j, d_out in enumerate(tuple(cfg.fc_sizes[1:]) + (cfg.n_classes,)):
        fcs.append({"w": dense_init(keys[n_conv + 1 + j], d_in, d_out),
                    "b": jnp.zeros((d_out,), jnp.float32)})
        d_in = d_out
    phi = {"fcs": tuple(fcs)}
    return gamma, phi


def cnn_client_forward(gamma: Params, cfg: CNNConfig, x: jnp.ndarray) -> jnp.ndarray:
    """x: (B, H, W, C) -> cut-layer activations (B, d_c)."""
    for p in gamma["convs"]:
        x = _maxpool2(jax.nn.relu(_conv(p, x, cfg.padding)))
    x = x.reshape(x.shape[0], -1)
    return jax.nn.relu(x @ gamma["cut_fc"]["w"] + gamma["cut_fc"]["b"])


def cnn_ap_forward(phi: Params, cfg: CNNConfig, acts: jnp.ndarray) -> jnp.ndarray:
    """Cut activations -> logits (B, n_classes)."""
    x = acts
    n = len(phi["fcs"])
    for i, p in enumerate(phi["fcs"]):
        x = x @ p["w"] + p["b"]
        if i < n - 1:
            x = jax.nn.relu(x)
    return x


def cnn_predict(gamma: Params, phi: Params, cfg: CNNConfig, x: jnp.ndarray) -> jnp.ndarray:
    return cnn_ap_forward(phi, cfg, cnn_client_forward(gamma, cfg, x))


def cnn_loss(gamma: Params, phi: Params, cfg: CNNConfig, x: jnp.ndarray,
             y: jnp.ndarray) -> jnp.ndarray:
    return cross_entropy(cnn_predict(gamma, phi, cfg, x), y)
