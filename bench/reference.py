"""The plain reference of a Pigeon-SL protocol run (Algorithm 1 of the
paper), written from the paper's description in straightforward
``jax.numpy``.  It imports nothing of the program and takes nothing the
program made: it draws the initial weights, the clusters, the mini-batch
indices and the per-step keys from the job's seed itself.  The model is the
configuration's kind (``kinds/<kind>.py``): its weights, its two forward
halves, its loss and its count of correct answers; this module holds the
protocol around it.

The draws follow the order the protocol defines for a seeded run:

* ``key = PRNGKey(seed)``; ``key, k0 = split(key)``; the model is
  initialised from ``k0`` (``kind.init_params``);
* per round, from ``numpy.random.default_rng(seed)``: a permutation of the M
  clients cut into R equal clusters (each sorted), then every client's
  (E, B) batch indices, cluster by cluster;
* per round, per cluster ``key, sub = split(key)``, per client
  ``sub, k_j = split(sub)``; per mini-batch ``k, s = split(k)`` and
  ``k_act, k_grad = split(s)``.

Each client turn is E SGD steps of the four-message exchange with the
malicious client's tamper applied where the paper puts it (labels and
activations before sending, the cut gradient after receiving).  A round
trains every cluster's chain from the same theta, scores each candidate by
its loss on D_o, takes the argmin, and re-checks the winner's hand-off
against its validation activations.

``dtype="float32"`` runs at ``Precision.HIGHEST`` (the reference);
``dtype="bfloat16"`` keeps weights, floating inputs and activations in
bfloat16 (the control, one precision below the configuration's).  ``fault``
plants one of the faults the check must catch, for reading its numbers:
``unchanged`` (theta never committed), ``half_batch`` (half of every
mini-batch left out), ``altered`` (the first cluster's validation loss
raised 1% where it is produced), ``wrong_pick`` (the worst candidate
selected).

``follow`` makes the reference take the program's selected cluster at each
round, so that the rounds after a near-tie are compared on one trajectory;
whether the program's choice was right is judged by ``select_excess``: how
far the chosen candidate's reference loss lies above the reference's best.
"""
from __future__ import annotations

import json
from functools import partial
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
LABEL_SHIFT = 3        # label_flip: y -> (y + 3) mod n_classes (the paper's)
ACT_KEEP = 0.1         # activation: keep 10% of the true cut activation
GRAD_SCALE = -1.0      # gradient: reverse the received cut gradient
FAULTS = (None, "unchanged", "half_batch", "altered", "wrong_pick")


def noise_blend(acts, key, keep):
    """Keep ``keep`` of the activation; fill the rest with Gaussian noise
    scaled to each sample's norm, taken over every axis but the first."""
    keep = jnp.float32(keep)
    n = jax.random.normal(key, acts.shape, jnp.float32)
    a32 = acts.astype(jnp.float32)
    axes = tuple(range(1, acts.ndim))
    g = jnp.sqrt(jnp.sum(a32 * a32, axis=axes, keepdims=True))
    nn = jnp.sqrt(jnp.sum(n * n, axis=axes, keepdims=True))
    out = keep * a32 + (1.0 - keep) * (n * (g / jnp.maximum(nn, 1e-12)))
    return out.astype(acts.dtype)


@partial(jax.jit, static_argnames=("kind", "model_key", "attack", "prec",
                                   "half"))
def _client_turn(gamma, phi, xs, ys, key, lr, *, kind, model_key, attack,
                 prec, half):
    model = json.loads(model_key)
    n_classes = kind.n_classes(model)

    def step(carry, batch):
        g, p, k = carry
        x, y = batch
        if half:
            x, y = x[: x.shape[0] // 2], y[: y.shape[0] // 2]
        k, s = jax.random.split(k)
        k_act, _ = jax.random.split(s)
        y_sent = (y + LABEL_SHIFT) % n_classes if attack == "label_flip" else y
        acts, vjp = jax.vjp(
            lambda gg: kind.client_forward(gg, x, model, prec), g)
        sent = noise_blend(acts, k_act, ACT_KEEP) if attack == "activation" \
            else acts
        loss, (g_phi, g_acts) = jax.value_and_grad(
            lambda pp, aa: kind.loss(kind.ap_logits(pp, aa, model, prec),
                                     y_sent),
            argnums=(0, 1))(p, sent)
        if attack == "gradient":
            g_acts = (GRAD_SCALE * g_acts.astype(jnp.float32)).astype(
                g_acts.dtype)
        (g_gamma,) = vjp(g_acts.astype(acts.dtype))
        upd = lambda w, d: w - lr * d.astype(w.dtype)
        return (jax.tree.map(upd, g, g_gamma), jax.tree.map(upd, p, g_phi),
                k), loss

    (g, p, _), losses = jax.lax.scan(step, (gamma, phi, key), (xs, ys))
    return g, p, jnp.mean(losses)


@partial(jax.jit, static_argnames=("kind", "model_key", "prec"))
def _validate(gamma, phi, x0, y0, *, kind, model_key, prec):
    model = json.loads(model_key)
    acts = kind.client_forward(gamma, x0, model, prec)
    return kind.loss(kind.ap_logits(phi, acts, model, prec), y0), acts


@partial(jax.jit, static_argnames=("kind", "model_key", "prec"))
def _recheck(gamma, x0, ref_acts, *, kind, model_key, prec):
    """The next round's first clients re-send g(x0, gamma received); the AP
    compares with the winner's validation activations."""
    recv = kind.client_forward(gamma, x0, json.loads(model_key),
                               prec).astype(jnp.float32)
    ref = ref_acts.astype(jnp.float32)
    return jnp.linalg.norm(recv - ref) / jnp.maximum(jnp.linalg.norm(ref),
                                                     1e-12)


@partial(jax.jit, static_argnames=("kind", "model_key", "prec"))
def _count_correct(gamma, phi, x, y, *, kind, model_key, prec):
    model = json.loads(model_key)
    acts = kind.client_forward(gamma, x, model, prec)
    return kind.count_correct(kind.ap_logits(phi, acts, model, prec), y)


def _inputs(a: np.ndarray, dt):
    """A device copy of model inputs: floating ones in the run's dtype,
    integer ones (labels, token ids) as they are."""
    return jnp.asarray(a, dt) if np.issubdtype(a.dtype, np.floating) \
        else jnp.asarray(a)


def run(kind, cfg: Dict, data, job, rounds: int, eval_every: int, *,
        dtype: str = "float32", fault: Optional[str] = None,
        follow: Optional[Sequence[int]] = None) -> List[Dict]:
    """The first ``rounds`` rounds of one job of a configuration whose model
    is of ``kind``: a list of records with ``val_losses``, ``train_losses``,
    ``selected``, ``accepted``, ``detections``, ``select_excess`` and, on
    eval rounds, ``test_acc`` (correct answers over ``kind.eval_units``)."""
    assert fault in FAULTS, fault
    model = cfg["model"]
    mk = json.dumps(model, sort_keys=True)
    dt = jnp.float32 if dtype == "float32" else jnp.bfloat16
    prec = HIGHEST if dtype == "float32" else None
    m, r = cfg["M"], cfg["N"] + 1
    e, b, lr = cfg["E"], cfg["B"], cfg["lr"]
    m_bar = m // r
    rng = np.random.default_rng(job.seed)
    key = jax.random.PRNGKey(job.seed)
    key, k0 = jax.random.split(key)
    theta = kind.init_params(k0, model, dt)
    x0, y0 = _inputs(data.x0, dt), jnp.asarray(data.y0)
    out = []
    for t in range(rounds):
        perm = rng.permutation(m)
        clusters = [sorted(perm[i * m_bar:(i + 1) * m_bar].tolist())
                    for i in range(r)]
        idx = [[rng.integers(0, data.x.shape[1], size=(e, b))
                for _ in cl] for cl in clusters]
        keys = []
        for _ in range(r):
            key, sub = jax.random.split(key)
            row = []
            for _ in range(m_bar):
                sub, kj = jax.random.split(sub)
                row.append(kj)
            keys.append(row)
        cands, vls, tls = [], [], []
        for i, cl in enumerate(clusters):
            g, p = theta
            losses = []
            for j, c in enumerate(cl):
                xs = _inputs(data.x[c][idx[i][j]], dt)
                ys = jnp.asarray(data.y[c][idx[i][j]])
                attack = job.attack if c in job.malicious else "none"
                g, p, loss = _client_turn(g, p, xs, ys, keys[i][j], lr,
                                          kind=kind, model_key=mk,
                                          attack=attack, prec=prec,
                                          half=fault == "half_batch")
                losses.append(float(loss))
            vl, acts = _validate(g, p, x0, y0, kind=kind, model_key=mk,
                                 prec=prec)
            cands.append((g, p, acts))
            vls.append(float(vl))
            tls.append(float(np.mean(losses)))
        if fault == "altered":
            vls[0] *= 1.01
        best = int(np.argmin(vls))
        sel = best if follow is None else int(follow[t])
        if fault == "wrong_pick":
            sel = int(np.argmax(vls))
        excess = (vls[sel] - vls[best]) / abs(vls[best])
        g, p, acts = cands[sel]
        dist = float(_recheck(g, x0, acts, kind=kind, model_key=mk,
                              prec=prec))
        accepted = dist <= cfg["tamper_tol"]
        if accepted and fault != "unchanged":
            theta = (g, p)
        rec = dict(round=t, val_losses=vls, train_losses=tls, selected=sel,
                   accepted=accepted, detections=0 if accepted else 1,
                   select_excess=excess)
        if t % eval_every == 0:
            correct = 0
            for s0 in range(0, data.x_test.shape[0], cfg["eval_batch"]):
                xb = _inputs(data.x_test[s0:s0 + cfg["eval_batch"]], dt)
                yb = jnp.asarray(data.y_test[s0:s0 + cfg["eval_batch"]])
                correct += int(_count_correct(theta[0], theta[1], xb, yb,
                                              kind=kind, model_key=mk,
                                              prec=prec))
            rec["test_acc"] = correct / kind.eval_units(data)
        out.append(rec)
    return out
