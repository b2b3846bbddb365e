"""Split-learning abstraction: the gamma/phi decomposition and the vanilla-SL
mini-batch message flow (FwdProp / BackProp of Algorithms 2 & 3).

A :class:`SplitModule` is the minimal interface the Pigeon-SL protocol needs:
any model that can be cut into a client half and an AP half fits (the paper's
CNNs, and every transformer family in ``repro.models`` via ``from_lm``).

``sl_minibatch_step`` reproduces the exact four-message exchange of the
paper, with attack hooks at each of the three tampering points:

  client --- g(x, gamma), y --->  AP        (activation + label messages)
  client <---  d loss / d c  ---  AP        (cut-layer gradient message)

implemented with ``jax.vjp`` so the client-side backward consumes exactly the
(possibly tampered) cut-layer gradient the AP sent — no gradient information
bypasses the cut.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .attacks import (Attack, AttackVec, flip_labels, flip_labels_vec,
                      poison_inputs, poison_inputs_vec, tamper_activation,
                      tamper_activation_vec, tamper_gradient,
                      tamper_gradient_vec)

Pytree = Any


@dataclasses.dataclass(frozen=True)
class SplitModule:
    """Pure-function view of a split model."""
    init: Callable[[jax.Array], Tuple[Pytree, Pytree]]
    client_forward: Callable[[Pytree, jnp.ndarray], jnp.ndarray]
    ap_loss: Callable[[Pytree, jnp.ndarray, jnp.ndarray], jnp.ndarray]
    predict: Callable[[Pytree, Pytree, jnp.ndarray], jnp.ndarray]
    n_classes: int = 10
    # conv layers whose filter gradient comes from flat patch rows
    # (``models/cnn.py::takes_patch_filter_grad``); the round-step spans
    # carry it as their ``patch_dw_layers`` attr
    patch_dw_layers: int = 0

    def loss(self, gamma, phi, x, y):
        return self.ap_loss(phi, self.client_forward(gamma, x), y)


def from_cnn(cfg) -> SplitModule:
    from ..models import cnn as cnn_mod

    return SplitModule(
        init=lambda key: cnn_mod.cnn_init(key, cfg),
        client_forward=lambda g, x: cnn_mod.cnn_client_forward(g, cfg, x),
        ap_loss=lambda p, a, y: _xent(cnn_mod.cnn_ap_forward(p, cfg, a), y),
        predict=lambda g, p, x: cnn_mod.cnn_predict(g, p, cfg, x),
        n_classes=cfg.n_classes,
        patch_dw_layers=cnn_mod.patch_filter_grad_layers(cfg),
    )


def from_lm(model) -> SplitModule:
    """Adapt a ``repro.models.Model`` (token batches) to the SplitModule
    interface: x = tokens (B, S); y = labels (B, S)."""

    def init(key):
        params = model.init(key)
        return model.split_params(params)

    def client_forward(gamma, tokens):
        return model.client_forward(gamma, {"tokens": tokens})

    def ap_loss(phi, acts, labels):
        b = labels.shape[0]
        loss, _ = model.ap_forward(phi, acts, {"tokens": labels, "labels": labels})
        return loss

    def predict(gamma, phi, tokens):
        params = model.merge_params(gamma, phi)
        return model.logits(params, {"tokens": tokens})

    return SplitModule(init=init, client_forward=client_forward, ap_loss=ap_loss,
                       predict=predict, n_classes=model.cfg.vocab)


def _xent(logits, y):
    from ..models.blocks import cross_entropy
    return cross_entropy(logits, y)


# ---------------------------------------------------------------------------
# AP-observable statistics of the transmitted activation message
# ---------------------------------------------------------------------------

MESSAGE_STAT_NAMES = ("dispersion", "support_residual")
N_MESSAGE_STATS = len(MESSAGE_STAT_NAMES)


def message_stats(acts_sent: jnp.ndarray) -> jnp.ndarray:
    """Per-batch anomaly statistics of a transmitted cut-activation message,
    computed from exactly what the AP observes (the post-tamper message):

      * ``dispersion`` — mean distance of the batch's samples from the batch
        mean, relative to the mean's norm.  A replayed message (one captured
        activation re-transmitted for the whole batch) has dispersion 0.
      * ``support_residual`` — norm fraction of the message outside the
        honest activation support (the paper's CNN cut layers are ReLU, so
        honest messages are non-negative; a noise blend leaves the support).
        Architectures without a constrained cut support yield near-equal
        residuals for every client, making the z-scored feature inert.

    These are the ``loss_plus_distance`` selection policy's activation
    distances (``repro.selection``): final-model validation activations carry
    no stealth/replay signal at small scale, but the training messages do.
    Returns a ``(N_MESSAGE_STATS,)`` f32 vector."""
    flat = acts_sent.reshape(acts_sent.shape[0], -1).astype(jnp.float32)
    mu = jnp.mean(flat, axis=0, keepdims=True)
    mu_norm = jnp.maximum(jnp.linalg.norm(mu), 1e-12)
    disp = jnp.mean(jnp.linalg.norm(flat - mu, axis=1)) / mu_norm
    total = jnp.maximum(jnp.linalg.norm(flat), 1e-12)
    support = jnp.linalg.norm(jnp.minimum(flat, 0.0)) / total
    return jnp.stack([disp, support])


# ---------------------------------------------------------------------------
# the SL mini-batch exchange with attack hooks
# ---------------------------------------------------------------------------

def _sl_exchange(module: SplitModule, gamma: Pytree, phi: Pytree,
                 x: jnp.ndarray, y: jnp.ndarray, key: jax.Array,
                 poison, send_labels, send_acts, recv_grad,
                 with_stats: bool = False, quant: Optional[str] = None):
    """One FwdProp/BackProp exchange.  Returns (g_gamma, g_phi, loss), plus
    the transmitted message's :func:`message_stats` when ``with_stats``.

    The attack hooks sit exactly where the taxonomy places them:
      * ``poison``: the client's own training inputs, before the forward
                                                    (backdoor trigger stamping)
      * ``send_labels``: labels tampered before transmission
                                                    (label flipping, backdoor)
      * ``send_acts``: cut activations tampered before transmission
                                                    (activation tampering, replay)
      * ``recv_grad``: cut gradient tampered after reception
                                                    (gradient scaling/noise)

    The per-exchange key splits into an activation-side and a gradient-side
    stream so stochastic attacks on either leg draw independent noise.

    ``quant`` compresses the two cut-layer wire messages through the
    ``kernels/quant_exchange`` round trip (per-sample symmetric int8 /
    fp8-e4m3, one f32 scale per row).  The transform models the physical
    wire: sender-side attacks (``send_acts``) apply *before* transmission and
    then quantize with the message — so the AP observes, scores and
    backpropagates through exactly the dequantized message a real receiver
    would reconstruct — while the client-side ``recv_grad`` hook applies
    *after* the cut gradient is dequantized.  Under ``with_stats`` the fused
    kernel emits :func:`message_stats` of that dequantized uplink message in
    the same pass, so anomaly scores stay free.

    Single source of truth for the four-message exchange: the static
    (per-``Attack``) and vectorised (per-``AttackVec``) entry points below
    differ only in which hook implementations they bind, so the engines'
    bit-for-bit equivalence contract cannot drift between two copies.
    """
    from ..kernels import ops as kops
    k_act, k_grad = jax.random.split(key)
    x_used = poison(x)
    y_sent = send_labels(y)

    acts, client_vjp = jax.vjp(lambda g: module.client_forward(g, x_used), gamma)
    acts_sent = send_acts(acts, k_act)
    stats = None
    if quant is not None:
        flat = acts_sent.reshape(acts_sent.shape[0], -1).astype(jnp.float32)
        if with_stats:
            deq, _, stats = kops.quant_roundtrip_stats(flat, quant)
        else:
            deq, _ = kops.quant_roundtrip(flat, quant)
        acts_sent = deq.reshape(acts_sent.shape).astype(acts_sent.dtype)

    def ap_fn(phi_, acts_):
        return module.ap_loss(phi_, acts_, y_sent)

    loss, ap_grads = jax.value_and_grad(ap_fn, argnums=(0, 1))(phi, acts_sent)
    g_phi, g_acts = ap_grads

    if quant is not None:
        gflat = g_acts.reshape(g_acts.shape[0], -1).astype(jnp.float32)
        gdeq, _ = kops.quant_roundtrip(gflat, quant)
        g_acts = gdeq.reshape(g_acts.shape).astype(g_acts.dtype)
    g_acts_recv = recv_grad(g_acts, k_grad)
    (g_gamma,) = client_vjp(g_acts_recv.astype(acts.dtype))
    if with_stats:
        if stats is None:
            stats = message_stats(acts_sent)
        return g_gamma, g_phi, loss, stats
    return g_gamma, g_phi, loss


def sl_minibatch_grads(module: SplitModule, attack: Attack, gamma: Pytree, phi: Pytree,
                       x: jnp.ndarray, y: jnp.ndarray, key: jax.Array,
                       with_stats: bool = False, quant: Optional[str] = None):
    """The exchange with a static ``Attack`` (one compiled program per spec)."""
    return _sl_exchange(
        module, gamma, phi, x, y, key,
        lambda x_: poison_inputs(attack, x_),
        lambda y_: flip_labels(attack, y_, module.n_classes),
        lambda a, k: tamper_activation(attack, a, k),
        lambda g, k: tamper_gradient(attack, g, k),
        with_stats=with_stats, quant=quant)


def sgd_update(params: Pytree, grads: Pytree, lr: float) -> Pytree:
    return jax.tree.map(lambda p, g: p - lr * g.astype(p.dtype), params, grads)


def _client_update(grads_fn, gamma: Pytree, phi: Pytree,
                   data: Tuple[jnp.ndarray, jnp.ndarray], lr: float,
                   key: jax.Array, with_stats: bool = False):
    """E mini-batch SGD updates for one client (lines 10-18 of Algorithm 1),
    generic over the exchange implementation.

    data = (xs, ys) with xs: (E, B, ...), ys: (E, B, ...).  With
    ``with_stats`` additionally returns the client's mean per-batch
    :func:`message_stats` vector (the ``grads_fn`` must return 4-tuples).
    """
    xs, ys = data

    def step(carry, inputs):
        gamma, phi, k = carry
        x, y = inputs
        k, sub = jax.random.split(k)
        out = grads_fn(gamma, phi, x, y, sub)
        g_gamma, g_phi, loss = out[:3]
        aux = (loss, out[3]) if with_stats else loss
        return (sgd_update(gamma, g_gamma, lr), sgd_update(phi, g_phi, lr), k), aux

    (gamma, phi, _), aux = jax.lax.scan(step, (gamma, phi, key), (xs, ys))
    if with_stats:
        losses, stats = aux
        return gamma, phi, jnp.mean(losses), jnp.mean(stats, axis=0)
    return gamma, phi, jnp.mean(aux)


@partial(jax.jit, static_argnums=(0, 1, 5), static_argnames=("quant",))
def client_update(module: SplitModule, attack: Attack, gamma: Pytree, phi: Pytree,
                  data: Tuple[jnp.ndarray, jnp.ndarray], lr: float, key: jax.Array,
                  *, quant: Optional[str] = None
                  ) -> Tuple[Pytree, Pytree, jnp.ndarray]:
    return _client_update(partial(sl_minibatch_grads, module, attack, quant=quant),
                          gamma, phi, data, lr, key)


@partial(jax.jit, static_argnums=(0, 1, 5), static_argnames=("quant",))
def client_update_stats(module: SplitModule, attack: Attack, gamma: Pytree,
                        phi: Pytree, data: Tuple[jnp.ndarray, jnp.ndarray],
                        lr: float, key: jax.Array, *,
                        quant: Optional[str] = None):
    """:func:`client_update` + the client's mean transmitted-message
    statistics — the sequential oracle's path for selection policies that
    score activation-message anomalies.  The parameter/loss arithmetic is
    bit-identical to :func:`client_update` (the stats ride alongside the
    same scan)."""
    return _client_update(
        partial(sl_minibatch_grads, module, attack, with_stats=True, quant=quant),
        gamma, phi, data, lr, key, with_stats=True)


# ---------------------------------------------------------------------------
# vectorised (vmappable) variants — the same exchange with the attack
# configuration as traced data instead of a static jit argument, so one
# compiled program serves every (cluster, client, attack) slot of the batched
# engine.  Honest slots reproduce ``client_update`` bit-for-bit: every tamper
# site is a ``jnp.where`` whose false branch is the untouched message.
# ---------------------------------------------------------------------------

def sl_minibatch_grads_vec(module: SplitModule, av: AttackVec, gamma: Pytree,
                           phi: Pytree, x: jnp.ndarray, y: jnp.ndarray,
                           key: jax.Array, with_stats: bool = False,
                           quant: Optional[str] = None):
    return _sl_exchange(
        module, gamma, phi, x, y, key,
        lambda x_: poison_inputs_vec(av, x_),
        lambda y_: flip_labels_vec(av, y_, module.n_classes),
        lambda a, k: tamper_activation_vec(av, a, k),
        lambda g, k: tamper_gradient_vec(av, g, k),
        with_stats=with_stats, quant=quant)


def client_update_vec_impl(module: SplitModule, av: AttackVec, gamma: Pytree,
                           phi: Pytree, data: Tuple[jnp.ndarray, jnp.ndarray],
                           lr: float, key: jax.Array, *,
                           quant: Optional[str] = None
                           ) -> Tuple[Pytree, Pytree, jnp.ndarray]:
    """Un-jitted body of :func:`client_update_vec` — the batched engine embeds
    it inside its own jitted round program (vmap over clusters, scan over the
    within-cluster client chain)."""
    return _client_update(partial(sl_minibatch_grads_vec, module, av, quant=quant),
                          gamma, phi, data, lr, key)


def client_update_vec_stats_impl(module: SplitModule, av: AttackVec,
                                 gamma: Pytree, phi: Pytree,
                                 data: Tuple[jnp.ndarray, jnp.ndarray],
                                 lr: float, key: jax.Array, *,
                                 quant: Optional[str] = None):
    """:func:`client_update_vec_impl` + mean message statistics (the batched
    engines' path for message-anomaly selection policies)."""
    return _client_update(
        partial(sl_minibatch_grads_vec, module, av, with_stats=True, quant=quant),
        gamma, phi, data, lr, key, with_stats=True)


client_update_vec = partial(jax.jit, static_argnums=(0, 5),
                            static_argnames=("quant",))(client_update_vec_impl)
