"""The ``cnn`` model kind: the paper's split CNNs (Section V-A), as the
benchmark builds them, draws their data, follows them in the plain
reference and counts their operations.

The system under test comes through the program's entry (``from_cnn``);
everything else here is written from the paper in plain ``numpy`` and
``jax.numpy`` and imports nothing of the program.

* Data: class-template images (each class a smooth random template, each
  sample ``amp * template + noise * N(0, 1)``), the scheme of the program's
  ``data/synthetic.py::make_classification_data`` kept here so that no
  change to the program can move the yardstick.  Client shards are
  ``(M, d_m, H, W, C)`` float32, labels int32.
* Weights: conv kernels truncated normal in [-2, 2] over
  ``sqrt(k*k*c_in)``, dense kernels truncated normal times ``1/sqrt(d_in)``,
  zero biases, drawn from one key split into one key per layer in order.
* Model: every conv is stride 1 with ReLU and 2x2 max-pooling; the client
  side ends with the cut FC layer (ReLU), the access point's FC layers give
  the logits.
* Accuracy is counted in images of the test set.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def build_module(cfg: Dict):
    """The program's SplitModule for the configuration, through ``from_cnn``."""
    from repro.core import from_cnn
    from repro.models.cnn import CNNConfig
    m = cfg["model"]
    return from_cnn(CNNConfig(
        name=cfg["name"], image_size=m["image_size"],
        in_channels=m["in_channels"],
        conv_channels=tuple(m["conv_channels"]), kernel=m["kernel"],
        padding=m["padding"], fc_sizes=tuple(m["fc_sizes"]),
        n_classes=m["n_classes"]))


def tiny_model(model: Dict) -> Dict:
    """The model block for the CPU tests' tiny cells: the paper's CNNs run
    a tiny round in seconds at their published widths, so unchanged."""
    return model


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

def _smooth(img: np.ndarray, k: int = 3, iters: int = 2) -> np.ndarray:
    """Box blur, so that the class templates are low-frequency images."""
    for _ in range(iters):
        pad = np.pad(img, (((k - 1) // 2, k // 2), ((k - 1) // 2, k // 2),
                           (0, 0)), mode="edge")
        acc = np.zeros_like(img)
        for dy in range(k):
            for dx in range(k):
                acc += pad[dy:dy + img.shape[0], dx:dx + img.shape[1], :]
        img = acc / (k * k)
    return img


def _sample(rng: np.random.Generator, templates: np.ndarray, n: int,
            noise: float) -> Tuple[np.ndarray, np.ndarray]:
    y = rng.integers(0, templates.shape[0], size=n).astype(np.int32)
    amp = rng.uniform(0.7, 1.3, size=(n, 1, 1, 1)).astype(np.float32)
    x = rng.standard_normal((n,) + templates.shape[1:], dtype=np.float32)
    x *= np.float32(noise)
    x += amp * templates[y]
    return x, y


def make_data(cfg: Dict, rng: np.random.Generator) -> Dict[str, np.ndarray]:
    """The cell's whole data set at the configuration's shapes (``model``,
    ``M``, ``d_m``, ``D_o``, ``n_test``, ``data.noise``), drawn from
    ``rng``: the templates, then each client's shard, D_o, the test set."""
    m = cfg["model"]
    s, c, k = m["image_size"], m["in_channels"], m["n_classes"]
    t = rng.standard_normal((k, s, s, c), dtype=np.float32)
    t = np.stack([_smooth(v) for v in t])
    t /= np.maximum(np.abs(t).max(axis=(1, 2, 3), keepdims=True), 1e-6)
    noise = cfg["data"]["noise"]
    xs = np.empty((cfg["M"], cfg["d_m"], s, s, c), np.float32)
    ys = np.empty((cfg["M"], cfg["d_m"]), np.int32)
    for i in range(cfg["M"]):
        xs[i], ys[i] = _sample(rng, t, cfg["d_m"], noise)
    x0, y0 = _sample(rng, t, cfg["D_o"], noise)
    xt, yt = _sample(rng, t, cfg["n_test"], noise)
    return dict(x=xs, y=ys, x0=x0, y0=y0, x_test=xt, y_test=yt)


def eval_units(data) -> int:
    """What ``test_acc`` counts: the test set's images."""
    return data.x_test.shape[0]


# ---------------------------------------------------------------------------
# the reference's model
# ---------------------------------------------------------------------------

def n_classes(model: Dict) -> int:
    return model["n_classes"]


def init_params(key, model: Dict, dtype):
    """(gamma, phi): the client side (convs, cut FC) and the AP side."""
    kk, pad = model["kernel"], model["padding"]
    convs_c, fc = model["conv_channels"], list(model["fc_sizes"])
    n_conv = len(convs_c)
    keys = jax.random.split(key, n_conv + len(fc) + 1)
    convs, c_in, s = [], model["in_channels"], model["image_size"]
    for i, c_out in enumerate(convs_c):
        w = jax.random.truncated_normal(keys[i], -2, 2, (kk, kk, c_in, c_out))
        w = (w / math.sqrt(kk * kk * c_in)).astype(jnp.float32)
        convs.append((w, jnp.zeros((c_out,), jnp.float32)))
        s = (s + 2 * pad - kk + 1) // 2
        c_in = c_out
    flat = s * s * c_in

    def dense(k, d_in, d_out):
        w = jax.random.truncated_normal(k, -2.0, 2.0, (d_in, d_out))
        return ((w * (1.0 / math.sqrt(d_in))).astype(jnp.float32),
                jnp.zeros((d_out,), jnp.float32))

    gamma = (tuple(convs), dense(keys[n_conv], flat, fc[0]))
    dims = fc + [model["n_classes"]]
    phi = tuple(dense(keys[n_conv + 1 + j], dims[j], dims[j + 1])
                for j in range(len(dims) - 1))
    return jax.tree.map(lambda a: a.astype(dtype), (gamma, phi))


def conv2d(x, w, pad: int, prec):
    """Stride-1 NHWC convolution with an HWIO kernel, as one matrix product
    over the k*k shifted copies of the zero-padded input."""
    k = w.shape[0]
    b, h, wd, c = x.shape
    xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    ho, wo = h + 2 * pad - k + 1, wd + 2 * pad - k + 1
    cols = [xp[:, dy:dy + ho, dx:dx + wo, :]
            for dy in range(k) for dx in range(k)]
    patches = jnp.stack(cols, axis=3).reshape(b, ho, wo, k * k * c)
    return jnp.dot(patches, w.reshape(k * k * c, -1), precision=prec)


def client_forward(gamma, x, model: Dict, prec):
    """The cut activation (B, fc_sizes[0]) of a batch of images."""
    convs, (w, b) = gamma
    pad = model["padding"]
    for cw, cb in convs:
        y = jnp.maximum(conv2d(x, cw, pad, prec) + cb, 0)
        x = jax.lax.reduce_window(y, -jnp.inf, jax.lax.max,
                                  (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    x = x.reshape(x.shape[0], -1)
    return jnp.maximum(jnp.dot(x, w, precision=prec) + b, 0)


def ap_logits(phi, a, model: Dict, prec):
    """The access point's logits (B, n_classes) from the cut activation."""
    for i, (w, b) in enumerate(phi):
        a = jnp.dot(a, w, precision=prec) + b
        if i < len(phi) - 1:
            a = jnp.maximum(a, 0)
    return a


def loss(logits, y):
    """Mean softmax cross-entropy over the batch, in float32."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    return jnp.mean(lse - jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0])


def count_correct(logits, y):
    """Images of the batch whose top logit is their label."""
    return jnp.sum(jnp.argmax(logits, axis=-1) == y)


# ---------------------------------------------------------------------------
# operation counts
# ---------------------------------------------------------------------------

def layer_macs(model: Dict) -> Tuple[List[int], List[int]]:
    """Per-sample multiply-adds of each layer of the split CNN:
    (client-side layers: convs then the cut FC, AP-side FC layers)."""
    s, c_in, k = model["image_size"], model["in_channels"], model["kernel"]
    pad = model["padding"]
    client = []
    for c_out in model["conv_channels"]:
        s_out = s + 2 * pad - k + 1          # stride 1
        client.append(s_out * s_out * c_out * k * k * c_in)
        s, c_in = s_out // 2, c_out          # 2x2 max-pool
    d = s * s * c_in
    fc = list(model["fc_sizes"]) + [model["n_classes"]]
    client.append(d * fc[0])                 # the cut layer
    ap = [fc[i] * fc[i + 1] for i in range(len(fc) - 1)]
    return client, ap


def forward_flops(model: Dict) -> Tuple[int, int]:
    """(client-side, whole-model) forward operations per sample."""
    client, ap = layer_macs(model)
    return 2 * sum(client), 2 * (sum(client) + sum(ap))
