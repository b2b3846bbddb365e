"""The plain reference of a Pigeon-SL protocol run (Algorithm 1 of the
paper, Section V-A's split CNNs), written from the paper's description in
straightforward ``jax.numpy``.  It imports nothing of the program and takes
nothing the program made: it draws the initial weights, the clusters, the
mini-batch indices and the per-step keys from the job's seed itself.

The draws follow the order the protocol defines for a seeded run:

* ``key = PRNGKey(seed)``; ``key, k0 = split(key)``; the CNN is initialised
  from ``k0`` (conv kernels truncated normal in [-2, 2] over
  ``sqrt(k*k*c_in)``, dense kernels truncated normal times
  ``1/sqrt(d_in)``, zero biases);
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
``dtype="bfloat16"`` keeps weights, data and activations in bfloat16 (the
control, one precision below the configuration's).  ``fault`` plants one of
the faults the check must catch, for reading its numbers: ``unchanged``
(theta never committed), ``half_batch`` (half of every mini-batch left
out), ``altered`` (the first cluster's validation loss raised 1% where it
is produced), ``wrong_pick`` (the worst candidate selected).

``follow`` makes the reference take the program's selected cluster at each
round, so that the rounds after a near-tie are compared on one trajectory;
whether the program's choice was right is judged by ``select_excess``: how
far the chosen candidate's reference loss lies above the reference's best.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
LABEL_SHIFT = 3        # label_flip: y -> (y + 3) mod 10 (the paper's attack)
ACT_KEEP = 0.1         # activation: keep 10% of the true cut activation
GRAD_SCALE = -1.0      # gradient: reverse the received cut gradient
FAULTS = (None, "unchanged", "half_batch", "altered", "wrong_pick")


def init_params(key, model: Dict, dtype):
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
    convs, (w, b) = gamma
    pad = model["padding"]
    for cw, cb in convs:
        y = jnp.maximum(conv2d(x, cw, pad, prec) + cb, 0)
        x = jax.lax.reduce_window(y, -jnp.inf, jax.lax.max,
                                  (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    x = x.reshape(x.shape[0], -1)
    return jnp.maximum(jnp.dot(x, w, precision=prec) + b, 0)


def ap_forward(phi, a, prec):
    for i, (w, b) in enumerate(phi):
        a = jnp.dot(a, w, precision=prec) + b
        if i < len(phi) - 1:
            a = jnp.maximum(a, 0)
    return a


def xent(logits, y):
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    return jnp.mean(lse - jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0])


def noise_blend(acts, key, keep):
    """Keep ``keep`` of the activation; fill the rest with Gaussian noise
    scaled to each sample's norm."""
    keep = jnp.float32(keep)
    n = jax.random.normal(key, acts.shape, jnp.float32)
    a32 = acts.astype(jnp.float32)
    g = jnp.sqrt(jnp.sum(a32 * a32, axis=1, keepdims=True))
    nn = jnp.sqrt(jnp.sum(n * n, axis=1, keepdims=True))
    out = keep * a32 + (1.0 - keep) * (n * (g / jnp.maximum(nn, 1e-12)))
    return out.astype(acts.dtype)


@partial(jax.jit, static_argnames=("model_key", "attack", "prec", "half"))
def _client_turn(gamma, phi, xs, ys, key, lr, *, model_key, attack, prec,
                 half):
    model = dict(model_key)
    n_classes = model["n_classes"]

    def step(carry, batch):
        g, p, k = carry
        x, y = batch
        if half:
            x, y = x[: x.shape[0] // 2], y[: y.shape[0] // 2]
        k, s = jax.random.split(k)
        k_act, _ = jax.random.split(s)
        y_sent = (y + LABEL_SHIFT) % n_classes if attack == "label_flip" else y
        acts, vjp = jax.vjp(lambda gg: client_forward(gg, x, model, prec), g)
        sent = noise_blend(acts, k_act, ACT_KEEP) if attack == "activation" \
            else acts
        loss, (g_phi, g_acts) = jax.value_and_grad(
            lambda pp, aa: xent(ap_forward(pp, aa, prec), y_sent),
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


@partial(jax.jit, static_argnames=("model_key", "prec"))
def _validate(gamma, phi, x0, y0, *, model_key, prec):
    model = dict(model_key)
    acts = client_forward(gamma, x0, model, prec)
    return xent(ap_forward(phi, acts, prec), y0), acts


@partial(jax.jit, static_argnames=("model_key", "prec"))
def _recheck(gamma, x0, ref_acts, *, model_key, prec):
    """The next round's first clients re-send g(x0, gamma received); the AP
    compares with the winner's validation activations."""
    recv = client_forward(gamma, x0, dict(model_key), prec).astype(jnp.float32)
    ref = ref_acts.astype(jnp.float32)
    return jnp.linalg.norm(recv - ref) / jnp.maximum(jnp.linalg.norm(ref),
                                                     1e-12)


@partial(jax.jit, static_argnames=("model_key", "prec"))
def _count_correct(gamma, phi, x, y, *, model_key, prec):
    model = dict(model_key)
    logits = ap_forward(phi, client_forward(gamma, x, model, prec), prec)
    return jnp.sum(jnp.argmax(logits, axis=-1) == y)


def _model_key(model: Dict):
    return tuple((k, tuple(v) if isinstance(v, list) else v)
                 for k, v in sorted(model.items()))


def run(cfg: Dict, data, job, rounds: int, eval_every: int, *,
        dtype: str = "float32", fault: Optional[str] = None,
        follow: Optional[Sequence[int]] = None) -> List[Dict]:
    """The first ``rounds`` rounds of one job: a list of records with
    ``val_losses``, ``train_losses``, ``selected``, ``accepted``,
    ``detections``, ``select_excess`` and, on eval rounds, ``test_acc``."""
    assert fault in FAULTS, fault
    model = cfg["model"]
    mk = _model_key(model)
    dt = jnp.float32 if dtype == "float32" else jnp.bfloat16
    prec = HIGHEST if dtype == "float32" else None
    m, r = cfg["M"], cfg["N"] + 1
    e, b, lr = cfg["E"], cfg["B"], cfg["lr"]
    m_bar = m // r
    rng = np.random.default_rng(job.seed)
    key = jax.random.PRNGKey(job.seed)
    key, k0 = jax.random.split(key)
    theta = init_params(k0, model, dt)
    x0, y0 = jnp.asarray(data.x0, dt), jnp.asarray(data.y0)
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
                xs = jnp.asarray(data.x[c][idx[i][j]], dt)
                ys = jnp.asarray(data.y[c][idx[i][j]])
                kind = job.attack if c in job.malicious else "none"
                g, p, loss = _client_turn(g, p, xs, ys, keys[i][j], lr,
                                          model_key=mk, attack=kind,
                                          prec=prec,
                                          half=fault == "half_batch")
                losses.append(float(loss))
            vl, acts = _validate(g, p, x0, y0, model_key=mk, prec=prec)
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
        dist = float(_recheck(g, x0, acts, model_key=mk, prec=prec))
        accepted = dist <= cfg["tamper_tol"]
        if accepted and fault != "unchanged":
            theta = (g, p)
        rec = dict(round=t, val_losses=vls, train_losses=tls, selected=sel,
                   accepted=accepted, detections=0 if accepted else 1,
                   select_excess=excess)
        if t % eval_every == 0:
            correct = 0
            for s0 in range(0, data.x_test.shape[0], cfg["eval_batch"]):
                xb = jnp.asarray(data.x_test[s0:s0 + cfg["eval_batch"]], dt)
                yb = jnp.asarray(data.y_test[s0:s0 + cfg["eval_batch"]])
                correct += int(_count_correct(theta[0], theta[1], xb, yb,
                                              model_key=mk, prec=prec))
            rec["test_acc"] = correct / data.x_test.shape[0]
        out.append(rec)
    return out
