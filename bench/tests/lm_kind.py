"""A decoder language model as a model kind, for the benchmark's tests only:
``test_tiny_size.py`` writes this file into a temporary root as
``bench/kinds/decoder.py``, so that it is never one of the benchmark's own
kinds.

The model is written once, here, in plain ``jax.numpy``: a token and
position embedding, one causal attention layer and an MLP (pre-norm,
residual) on each side of the cut, then a final norm and the head.
``build_module`` wraps these same functions in the program's
``SplitModule``, so the program under test is the protocol engine, and the
reference follows the same functions at ``Precision.HIGHEST``.

* Data: token sequences of a seeded first-order chain over the vocabulary
  (each token has 4 successors, drawn uniformly); x is a sequence of
  ``seq_len`` tokens, y the same sequence one token on.  Client shards are
  ``(M, d_m, seq_len)`` int32.
* Weights: normal over the square root of its rows for every matrix, the
  embeddings too, and ones for the norms' scales; one key per leaf, in the
  order of the flattened tree.
* Accuracy is counted in tokens of the test set.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

SUCCESSORS = 4


def tiny_model(model: Dict) -> Dict:
    """The same layers at widths the CPU runs a round of in seconds."""
    return dict(model, d_model=64, n_heads=4, d_ff=256, seq_len=16,
                vocab=128)


def build_module(cfg: Dict):
    """The program's SplitModule around this file's functions."""
    from repro.core import SplitModule
    m = cfg["model"]
    return SplitModule(
        init=lambda key: init_params(key, m, jnp.float32),
        client_forward=lambda g, x: client_forward(g, x, m, None),
        ap_loss=lambda p, a, y: loss(ap_logits(p, a, m, None), y),
        predict=lambda g, p, x: ap_logits(p, client_forward(g, x, m, None),
                                          m, None),
        n_classes=m["vocab"])


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

def _sequences(rng: np.random.Generator, succ: np.ndarray, n: int,
               s: int) -> Tuple[np.ndarray, np.ndarray]:
    toks = np.empty((n, s + 1), np.int32)
    toks[:, 0] = rng.integers(0, succ.shape[0], size=n)
    for t in range(s):
        pick = rng.integers(0, SUCCESSORS, size=n)
        toks[:, t + 1] = succ[toks[:, t], pick]
    return toks[:, :-1], toks[:, 1:]


def make_data(cfg: Dict, rng: np.random.Generator) -> Dict[str, np.ndarray]:
    """The chain, then each client's shard, D_o and the test set."""
    m = cfg["model"]
    v, s = m["vocab"], m["seq_len"]
    succ = rng.integers(0, v, size=(v, SUCCESSORS)).astype(np.int32)
    xs = np.empty((cfg["M"], cfg["d_m"], s), np.int32)
    ys = np.empty_like(xs)
    for i in range(cfg["M"]):
        xs[i], ys[i] = _sequences(rng, succ, cfg["d_m"], s)
    x0, y0 = _sequences(rng, succ, cfg["D_o"], s)
    xt, yt = _sequences(rng, succ, cfg["n_test"], s)
    return dict(x=xs, y=ys, x0=x0, y0=y0, x_test=xt, y_test=yt)


def eval_units(data) -> int:
    """What ``test_acc`` counts: the test set's tokens."""
    return data.y_test.size


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def n_classes(model: Dict) -> int:
    return model["vocab"]


def init_params(key, model: Dict, dtype):
    """(gamma, phi): the embeddings and the first layer; the second layer,
    the final norm and the head."""
    d, v, s, ff = (model["d_model"], model["vocab"], model["seq_len"],
                   model["d_ff"])
    layer = dict(norm1=(d,), wq=(d, d), wk=(d, d), wv=(d, d), wo=(d, d),
                 norm2=(d,), w_in=(d, ff), w_out=(ff, d))
    shapes = ({"emb": (v, d), "pos": (s, d), "layer": layer},
              {"layer": layer, "norm_f": (d,), "head": (d, v)})
    leaves, tree = jax.tree.flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple)
        and isinstance(x[0], int))
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(tree, [
        jnp.ones(sh, dtype) if len(sh) == 1 else
        (jax.random.normal(k, sh) / math.sqrt(sh[0])).astype(dtype)
        for k, sh in zip(keys, leaves)])


def _norm(x, scale):
    x32 = x.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + 1e-6)
    return (x32 * r).astype(x.dtype) * scale


def _layer(p, h, n_heads: int, prec):
    """One pre-norm block: causal self-attention, then the MLP."""
    b, s, d = h.shape
    hd = d // n_heads
    x = _norm(h, p["norm1"])
    q, k, v = (jnp.dot(x, p[w], precision=prec).reshape(b, s, n_heads, hd)
               for w in ("wq", "wk", "wv"))
    scores = jnp.einsum("bqhe,bkhe->bhqk", q, k, precision=prec)
    scores = scores.astype(jnp.float32) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    w = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
    o = jnp.einsum("bhqk,bkhe->bqhe", w.astype(v.dtype), v, precision=prec)
    h = h + jnp.dot(o.reshape(b, s, d), p["wo"], precision=prec)
    x = _norm(h, p["norm2"])
    u = jax.nn.gelu(jnp.dot(x, p["w_in"], precision=prec))
    return h + jnp.dot(u, p["w_out"], precision=prec)


def client_forward(gamma, x, model: Dict, prec):
    """The cut activation (B, S, d) of a batch of token sequences."""
    h = jnp.take(gamma["emb"], x, axis=0) + gamma["pos"][: x.shape[1]]
    return _layer(gamma["layer"], h, model["n_heads"], prec)


def ap_logits(phi, a, model: Dict, prec):
    """The access point's logits (B, S, vocab) from the cut activation."""
    h = _layer(phi["layer"], a, model["n_heads"], prec)
    return jnp.dot(_norm(h, phi["norm_f"]), phi["head"], precision=prec)


def loss(logits, y):
    """Mean softmax cross-entropy over every token, in float32."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    return jnp.mean(lse - jnp.take_along_axis(logits, y[..., None],
                                              axis=-1)[..., 0])


def count_correct(logits, y):
    """Tokens whose top logit is their label."""
    return jnp.sum(jnp.argmax(logits, axis=-1) == y)


# ---------------------------------------------------------------------------
# operation counts
# ---------------------------------------------------------------------------

def _layer_macs(model: Dict) -> int:
    d, s, ff = model["d_model"], model["seq_len"], model["d_ff"]
    return s * (4 * d * d + 2 * d * ff) + 2 * s * s * d


def forward_flops(model: Dict) -> Tuple[int, int]:
    """(client-side, whole-model) forward operations per sequence."""
    client = _layer_macs(model)
    ap = _layer_macs(model) + model["seq_len"] * model["d_model"] \
        * model["vocab"]
    return 2 * client, 2 * (client + ap)
