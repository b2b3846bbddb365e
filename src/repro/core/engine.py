"""Batched cluster-parallel protocol engine.

Pigeon-SL's global round trains R = N+1 clusters independently from the same
theta^t — embarrassingly parallel work that the sequential driver in
``protocol.py`` dispatches one ``client_update`` at a time.  This module
stacks the R clusters' sampled batches, per-client attack state and RNG keys
into leading-axis arrays and runs the whole round as ONE compiled program via
the placement-aware :class:`~repro.core.runner.RoundRunner` — ``jax.vmap``
over clusters on one device (``placement="vmap"``) or the cluster axis laid
over a device mesh (``placement="sharded"``), with ``jax.lax.scan`` over each
within-cluster client chain and the shared-set validation forward (plus the
tamper-check activations it produces) mapped alongside.  A second seed level
turns the round program into a multi-seed sweep that advances S whole
protocol replicas in lockstep — nested ``vmap`` on one device, or the S x R
replica grid over a 2-D ``(seed, pod)`` mesh under ``placement="sharded"``.
SplitFed binds the same runner with a per-cluster *parallel* client vmap and
the FedAvg ``combine`` fan-in instead of the client-chain scan.

Equivalence contract with the sequential engine (tested in
``tests/test_engine.py`` / ``tests/test_runner.py``): both engines — under
either placement — consume the numpy batch-sampling RNG and the JAX key
stream in exactly the same order, the attack transforms are
``jnp.where``-masked versions of the same arithmetic, and the CommMeter
accounting goes through the same ``account_client_turn`` helper — so seeded
runs select the same clusters, produce validation losses equal within float
tolerance, and report bit-identical message counts.
"""
from __future__ import annotations

import dataclasses
import threading
from functools import lru_cache, partial
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..adversary import ThreatModel, resolve_threat_model
from .attacks import HONEST, Attack
from .clustering import cluster_is_honest, make_clusters
from .protocol import (ClientData, CommMeter, History, ProtocolConfig,
                       _count_params, account_client_turn,
                       account_handoff_recheck, account_param_transfer,
                       account_validation, cut_width, eval_span,
                       sample_batch_idx)
from .runner import (cluster_map, onehot_select, protocol_accept_runner,
                     protocol_round_spec, protocol_runner)
from .split import (SplitModule, client_update_vec_impl,
                    client_update_vec_stats_impl)
from ..telemetry import NULL_SESSION

Pytree = Any


# ---------------------------------------------------------------------------
# round assembly: indices drawn on the host, mini-batches gathered on the
# device from a resident copy of the client shards
# ---------------------------------------------------------------------------

_RESIDENT_LOCK = threading.Lock()


def _flat_per_sample(a: np.ndarray) -> np.ndarray:
    """(M, D_m, ...) -> (M*D_m, F), F the product of the sample dims, or
    (M*D_m,) for scalar samples.  The chip tiles an array's two minor dims
    to (8, 128): an image's channel dim (1 or 3) as the minor dim would pad
    the copy up to ~40x, a row of F floats hardly at all."""
    rows = (a.shape[0] * a.shape[1],)
    return a.reshape(rows + ((int(np.prod(a.shape[2:])),) if a.ndim > 2
                             else ()))


def resident_data(data: ClientData, telemetry=NULL_SESSION
                  ) -> Tuple[jax.Array, jax.Array]:
    """The device copy of every client shard that the batched engine gathers
    its mini-batches from: ``x`` flat per sample, ``y`` as (M*D_m, ...).
    Put once per ``ClientData`` object, under the fenced
    ``assemble.resident`` span (``h2d_bytes``), and memoised on it, so every
    later round, run and job on the same object reads the one copy."""
    with _RESIDENT_LOCK:
        got = data._resident
        if got is None or got[0] is not data.x or got[1] is not data.y:
            x, y = _flat_per_sample(data.x), _flat_per_sample(data.y)
            with telemetry.span("assemble.resident",
                                h2d_bytes=x.nbytes + y.nbytes) as sp:
                res = jnp.asarray(x), jnp.asarray(y)
                sp.fence(res)
            got = data._resident = (data.x, data.y) + res
    return got[2:]


def draw_round_idx(rng: np.random.Generator, data: ClientData,
                   clusters: Sequence[Sequence[int]], pcfg: ProtocolConfig,
                   out: Optional[np.ndarray] = None,
                   telemetry=NULL_SESSION) -> np.ndarray:
    """Every client's (E, B) mini-batch indices for the round, consuming the
    numpy RNG in the sequential engine's order (cluster-major, then client),
    as rows of :func:`resident_data`'s copy (``client * D_m + idx``): an
    int32 (R, M_bar, E, B) buffer, or ``out``, one round's view of a
    block's or a pool block's buffer.  Runs under an ``assemble.gather``
    span."""
    d_m = data.x.shape[1]
    if out is None:
        out = np.empty((len(clusters), len(clusters[0]), pcfg.E, pcfg.B),
                       dtype=np.int32)
    with telemetry.span("assemble.gather"):
        for i, cluster in enumerate(clusters):
            for j, client in enumerate(cluster):
                out[i, j] = client * d_m + sample_batch_idx(rng, d_m, pcfg.E,
                                                            pcfg.B)
    return out


def put_idx(idx: np.ndarray, telemetry=NULL_SESSION, **attrs) -> jax.Array:
    """The host->device copy of drawn indices, under the ``assemble.put``
    span: fenced to ready, with the bytes moved as ``h2d_bytes``."""
    with telemetry.span("assemble.put", h2d_bytes=idx.nbytes, **attrs) as sp:
        idx = jnp.asarray(idx)
        sp.fence(idx)
    return idx


@partial(jax.jit, static_argnums=(3, 4))
def _take_rows(x_res, y_res, idx, x_shape, y_shape):
    # "clip": the drawn rows are in range; the default "fill" would mask
    # every gathered element
    flat = idx.reshape(-1)
    return (jnp.take(x_res, flat, axis=0, mode="clip").reshape(
                idx.shape + x_shape),
            jnp.take(y_res, flat, axis=0, mode="clip").reshape(
                idx.shape + y_shape))


def take_batches(data: ClientData, idx: jax.Array, telemetry=NULL_SESSION,
                 **attrs) -> Tuple[jax.Array, jax.Array]:
    """The mini-batches at device indices ``idx`` (any leading shape: a
    round, a K-round block, a J-lane pool block), shaped ``idx.shape +
    x.shape[2:]`` like the host arrays they stand for, gathered by one
    jitted call under the ``assemble.gather`` span (``device_bytes``: the
    bytes it writes).  Not fenced: the gather queues behind the round
    program the device is running, and a fence would time that."""
    x_res, y_res = resident_data(data, telemetry)
    row_bytes = (x_res.nbytes + y_res.nbytes) // x_res.shape[0]
    with telemetry.span("assemble.gather", device_bytes=idx.size * row_bytes,
                        **attrs):
        return _take_rows(x_res, y_res, idx, data.x.shape[2:],
                          data.y.shape[2:])


def gather_batches(data: ClientData, idx: np.ndarray, telemetry=NULL_SESSION,
                   **attrs) -> Tuple[jax.Array, jax.Array]:
    """:func:`put_idx` then :func:`take_batches`: host indices in, device
    mini-batches out."""
    return take_batches(data, put_idx(idx, telemetry, **attrs), telemetry,
                        **attrs)


def assemble_round_batches(rng: np.random.Generator, data: ClientData,
                           clusters: Sequence[Sequence[int]],
                           pcfg: ProtocolConfig, telemetry=NULL_SESSION
                           ) -> Tuple[jax.Array, jax.Array]:
    """Every client's (E, B) mini-batches for the round, stacked to
    (R, M_bar, E, B, ...) on the device: the indices drawn on the host in
    the sequential engine's RNG order (:func:`draw_round_idx`), put, and
    gathered from the resident shards (:func:`gather_batches`)."""
    return gather_batches(data, draw_round_idx(rng, data, clusters, pcfg,
                                               telemetry=telemetry),
                          telemetry)


@partial(jax.jit, static_argnums=(1, 2))
def _round_client_keys(key: jax.Array, r: int, m_bar: int
                       ) -> Tuple[jax.Array, jax.Array]:
    rows = []
    for _ in range(r):
        key, sub = jax.random.split(key)
        row = []
        for _ in range(m_bar):
            sub, k_j = jax.random.split(sub)
            row.append(k_j)
        rows.append(jnp.stack(row))
    return key, jnp.stack(rows)


def round_client_keys(key: jax.Array, clusters: Sequence[Sequence[int]]
                      ) -> Tuple[jax.Array, jax.Array]:
    """Replicate the sequential engine's key discipline — per cluster
    ``key, sub = split(key)``, then per client ``sub, k_j = split(sub)`` —
    and stack the per-client keys to (R, M_bar, key).  Returns the advanced
    protocol key so both engines stay on the same stream.  The whole split
    chain runs as one jitted call instead of R + M host dispatches."""
    return _round_client_keys(key, len(clusters), len(clusters[0]))


def _assemble_with(split_keys, rng, key, data, clusters, pcfg, tm, t, out,
                   telemetry):
    if out is None:
        batches = assemble_round_batches(rng, data, clusters, pcfg, telemetry)
    else:
        draw_round_idx(rng, data, clusters, pcfg, out, telemetry)
        batches = ()
    key, keys = split_keys(key, clusters)
    return key, (*batches, tm.attack_vec_for_clusters(clusters, t), keys)


def assemble_round(rng: np.random.Generator, key: jax.Array, data: ClientData,
                   clusters: Sequence[Sequence[int]], pcfg: ProtocolConfig,
                   tm: ThreatModel, t: int, out=None, telemetry=NULL_SESSION):
    """One round's complete payload: stacked batches, derived per-client
    keys and the round's AttackVec.  THE single copy of the RNG/key
    consumption order — the synchronous path, the RoundFeeder's background
    thread AND the round-block assembler all call this, so the bit-identical
    prefetch-on/off and block-on/off contracts are structural rather than
    test-enforced.  ``telemetry`` gets the draw, put and gather spans; the
    key split and the attack lanes are the rest of the caller's assembly
    span.  Returns (advanced_key, (xs, ys, avec, keys)).

    ``out`` — one round's view of a block's index buffer — takes the drawn
    indices and leaves the gather to the block's caller: then the payload
    is just ``(avec, keys)``."""
    return _assemble_with(round_client_keys, rng, key, data, clusters, pcfg,
                          tm, t, out, telemetry)


# ---------------------------------------------------------------------------
# the compiled round program (single source of truth: core/runner.py)
# ---------------------------------------------------------------------------

def _round_body(module: SplitModule, lr: float, gamma: Pytree, phi: Pytree,
                xs, ys, avec, keys, x0, y0):
    """All R clusters' client chains + shared-set validation — a thin adapter
    over the RoundRunner's :func:`~repro.core.runner.cluster_map` (the one
    copy of the round math) keeping the historical flat signature.

    xs/ys: (R, M_bar, E, B, ...); avec leaves and keys: (R, M_bar, ...).
    Returns (gammas, phis, train_losses (R, M_bar), val_losses (R,),
    val_acts (R, D_o, d_c)) — the R candidate round outcomes.
    """
    (gs, ps), losses, vlosses, vacts = cluster_map(
        protocol_round_spec(module, lr), (gamma, phi),
        (xs, ys, avec, keys), (x0, y0))
    return gs, ps, losses, vlosses, vacts


batched_round = partial(jax.jit, static_argnums=(0, 1))(_round_body)


# ---------------------------------------------------------------------------
# protocol-facing drivers (same result structure as the sequential loops)
# ---------------------------------------------------------------------------

def train_round_batched(module: SplitModule, theta, clusters, data: ClientData,
                        pcfg: ProtocolConfig, tm: ThreatModel, t: int,
                        rng: np.random.Generator, key: jax.Array, meter: CommMeter,
                        d_c: int, x0, y0, placement: str = "vmap",
                        prefetched=None, with_stats: bool = False,
                        telemetry=None
                        ) -> Tuple[jax.Array, List[Dict[str, Any]]]:
    """Batched replacement for the sequential per-cluster loop of
    ``run_pigeon``: one compiled call produces all R candidate
    (gamma, phi, val_loss, val_acts) tuples, selection left to the host-side
    reference cascade (``repro.selection.select_host`` — the param-tamper
    path; the default path is :func:`pigeon_round_accept`).  The threat
    model's per-round attack state arrives as AttackVec *data*, so
    heterogeneous mixtures and schedule phases reuse the same compiled
    program; ``placement`` picks the RoundRunner's device mapping
    (single-device vmap or the cluster axis sharded over a host/pod mesh).
    ``prefetched`` carries a round payload assembled ahead of time by the
    RoundFeeder (``data/pipeline.py``) — when given, the RNG/key streams
    were already consumed by the feeder thread in this exact order.
    ``with_stats`` additionally surfaces per-client transmitted-message
    statistics in each result (anomaly-scoring selection policies)."""
    tel = NULL_SESSION if telemetry is None else telemetry
    if prefetched is None:
        with tel.span("round.assemble", round=t):
            key, prefetched = assemble_round(rng, key, data, clusters, pcfg,
                                             tm, t, telemetry=tel)
    xs, ys, avec, keys = prefetched
    with tel.span("round.step", round=t,
                  patch_dw_layers=module.patch_dw_layers) as sp:
        (gs, ps), aux, vlosses, vacts = protocol_runner(
            module, pcfg.lr, placement, with_stats,
            quant=pcfg.comm.quant).candidates(
            theta, (xs, ys, avec, keys), (x0, y0))
        sp.fence(vlosses)
    losses, stats = (aux if with_stats else (aux, None))

    d_cl = _count_params(theta[0])
    for cluster in clusters:
        for j in range(len(cluster)):
            account_client_turn(meter, pcfg, d_c, d_cl, handoff=j < len(cluster) - 1)

    losses = np.asarray(losses)
    vlosses = np.asarray(vlosses)
    stats = None if stats is None else np.asarray(stats)
    results = []
    for r, cluster in enumerate(clusters):
        # gamma/phi/vacts stay as views into the stacked arrays; the
        # selection loop materialises only the candidates it inspects
        # (protocol.res_params / res_vacts).
        res = dict(vloss=float(vlosses[r]), cluster=cluster,
                   train_loss=float(np.mean(losses[r])),
                   _stacked=(gs, ps, vacts, r))
        if stats is not None:
            res["msg_stats"] = stats[r]
        results.append(res)
    return key, results


def pigeon_round_accept(module: SplitModule, theta, clusters, data: ClientData,
                        pcfg: ProtocolConfig, tm: ThreatModel, t: int,
                        rng: np.random.Generator, key: jax.Array,
                        meter: CommMeter, d_c: int, x0, y0, policy,
                        placement: str = "vmap", prefetched=None,
                        telemetry=None):
    """The default batched round: training, validation AND the whole
    acceptance cascade (policy score -> rank -> handoff verify -> commit)
    in one compiled program, with a single stacked host fetch.  Returns
    ``(key, theta', record)`` where ``record`` carries the History fields
    (val_losses / train_losses / selected / detections / accepted).

    Only callable when the threat model mounts no handoff (param-tamper)
    attacks — those split the protocol key per *visited* candidate, which is
    inherently host-sequenced (``repro.selection.select_host``)."""
    from ..selection import unpack_fetch
    assert not tm.has_param_tamper, \
        "param-tamper threat models must use the host selection cascade"
    tel = NULL_SESSION if telemetry is None else telemetry
    if prefetched is None:
        with tel.span("round.assemble", round=t):
            key, prefetched = assemble_round(rng, key, data, clusters, pcfg,
                                             tm, t, telemetry=tel)
    runner = protocol_accept_runner(module, pcfg.lr, placement, policy,
                                    pcfg.tamper_check, pcfg.tamper_tol,
                                    quant=pcfg.comm.quant)
    with tel.span("round.step", round=t,
                  patch_dw_layers=module.patch_dw_layers) as sp:
        theta_next, fetch = runner.accept(theta, prefetched, (x0, y0))
        # fence the fetch only: the step span absorbs the device round
        # (block_until_ready waits, it does not transfer), leaving the fetch
        # span below with just the D2H copy — still ONE host sync per round
        sp.fence(fetch)

    d_cl = _count_params(theta[0])
    for cluster in clusters:
        for j in range(len(cluster)):
            account_client_turn(meter, pcfg, d_c, d_cl,
                                handoff=j < len(cluster) - 1)

    with tel.span("round.fetch", round=t):
        vlosses, tlosses, selected, detections, accepted = unpack_fetch(
            np.asarray(fetch), len(clusters))      # the round's one host sync
    with tel.span("round.select", round=t):
        # Table I accounting for the handoff re-checks: one R-recipient
        # re-transmission per visited candidate, exactly as the host cascade
        # charges per visit (detections failures + the accepted one).
        if pcfg.tamper_check:
            visited = detections + (1 if accepted else 0)
            account_handoff_recheck(meter, pcfg, int(x0.shape[0]), d_c,
                                    visited)
        record = dict(val_losses=[float(v) for v in vlosses],
                      train_losses=[float(v) for v in tlosses],
                      selected=selected, detections=detections,
                      accepted=accepted)
    return key, theta_next, record


def train_cluster_batched(module: SplitModule, theta, cluster, data: ClientData,
                          pcfg: ProtocolConfig, tm: ThreatModel, t: int,
                          rng: np.random.Generator, key: jax.Array,
                          meter: CommMeter, d_c: int, telemetry=NULL_SESSION
                          ) -> Tuple[jax.Array, Pytree, Pytree, float]:
    """One cluster's client chain as a single compiled call (used for the
    Pigeon-SL+ sub-rounds; always the vmap placement — a single cluster has
    no cluster axis to shard).  Key/RNG consumption matches the sequential
    ``split(key)`` + ``train_cluster`` pair exactly."""
    key, payload = assemble_round(rng, key, data, [cluster], pcfg, tm, t,
                                  telemetry=telemetry)
    (gs, ps), losses, _, _ = protocol_runner(
        module, pcfg.lr, "vmap", quant=pcfg.comm.quant).candidates(
        theta, payload,
        (jnp.asarray(data.x0[:1]), jnp.asarray(data.y0[:1])))
    d_cl = _count_params(theta[0])
    for j in range(len(cluster)):
        account_client_turn(meter, pcfg, d_c, d_cl, handoff=j < len(cluster) - 1)
    g = jax.tree.map(lambda a: a[0], gs)
    p = jax.tree.map(lambda a: a[0], ps)
    return key, g, p, float(np.mean(np.asarray(losses)))


# ---------------------------------------------------------------------------
# SplitFed: all M clients update in parallel (no within-cluster chain)
# ---------------------------------------------------------------------------

@lru_cache(maxsize=None)
def splitfed_round_spec(module: SplitModule, lr: float,
                        with_stats: bool = False,
                        quant: Optional[str] = None) -> "RoundSpec":
    """SplitFed's per-cluster programs as a RoundRunner binding: every client
    trains *in parallel* from the cluster's incoming theta (vmap over the
    client axis, vs the Pigeon chain's scan), the RoundSpec ``combine`` hook
    FedAvg-fans the per-client results into the cluster model, and shared-set
    validation is identical to the Pigeon spec.  Binding through the runner
    gives SplitFed both placements, the prefetch pipeline and the pluggable
    selection policies for free — there is no bespoke SplitFed round body any
    more.  No ``handoff_acts`` hook: SplitFed has no chained parameter
    handoff, so the fused cascade's verify stage stays disabled."""
    from .runner import RoundSpec

    def train_cluster(theta, inputs):
        xs_c, ys_c, av_c, keys_c = inputs
        gamma, phi = theta

        def per_client(x, y, av, k):
            if with_stats:
                g, p, loss, stats = client_update_vec_stats_impl(
                    module, av, gamma, phi, (x, y), lr, k, quant=quant)
                return (g, p), (loss, stats)
            g, p, loss = client_update_vec_impl(module, av, gamma, phi,
                                                (x, y), lr, k, quant=quant)
            return (g, p), loss

        (gs, ps), aux = jax.vmap(per_client)(xs_c, ys_c, av_c, keys_c)
        return (gs, ps), aux

    def fedavg(theta):
        return jax.tree.map(lambda a: jnp.mean(a, axis=0), theta)

    def validate(theta, val):
        g, p = theta
        x0, y0 = val
        acts = module.client_forward(g, x0)
        # val_aux None: SplitFed has no handoff tamper check, so the
        # (R, D_o, d_c) activation stack would be dead weight every round
        return module.ap_loss(p, acts, y0), None

    def validate_sharded(theta, val, k):
        from .runner import sharded_validation_losses
        g, p = theta
        x0, y0 = val
        acts = module.client_forward(g, x0)
        shard_losses = sharded_validation_losses(module, p, acts, y0, k)
        return module.ap_loss(p, acts, y0), shard_losses, None

    from .runner import make_train_summary
    return RoundSpec(
        train_cluster, validate, combine=fedavg,
        validate_sharded=validate_sharded,
        train_summary=make_train_summary(with_stats),
        message_stats=(lambda aux: aux[1]) if with_stats else None)


@lru_cache(maxsize=None)
def splitfed_runner(module: SplitModule, lr: float, placement: str = "vmap",
                    with_stats: bool = False, quant: Optional[str] = None):
    """Cached per (module, lr, placement, stats, quant), like
    :func:`protocol_runner`."""
    from .runner import RoundRunner
    return RoundRunner(splitfed_round_spec(module, lr, with_stats, quant),
                       placement=placement)


@lru_cache(maxsize=None)
def splitfed_accept_runner(module: SplitModule, lr: float, placement: str,
                           select, quant: Optional[str] = None):
    """SplitFed's fused-selection runner: the policy cascade with the verify
    stage off (no chained handoff to tamper with)."""
    from .runner import RoundRunner, VerifyConfig
    spec = splitfed_round_spec(module, lr,
                               with_stats=select.needs_message_stats,
                               quant=quant)
    return RoundRunner(spec, placement=placement, select=select,
                       verify=VerifyConfig(enabled=False))


@partial(jax.jit, static_argnums=(1, 2))
def _splitfed_keys(key: jax.Array, r: int, m_bar: int
                   ) -> Tuple[jax.Array, jax.Array]:
    rows = []
    for _ in range(r):
        row = []
        for _ in range(m_bar):
            key, sub = jax.random.split(key)
            row.append(sub)
        rows.append(jnp.stack(row))
    return key, jnp.stack(rows)


def splitfed_keys(key: jax.Array, clusters: Sequence[Sequence[int]]
                  ) -> Tuple[jax.Array, jax.Array]:
    """SplitFed's sequential loop splits the running protocol key once per
    client (cluster-major order) with no per-cluster sub-stream."""
    return _splitfed_keys(key, len(clusters), len(clusters[0]))


def assemble_splitfed_round(rng: np.random.Generator, key: jax.Array,
                            data: ClientData,
                            clusters: Sequence[Sequence[int]],
                            pcfg: ProtocolConfig, tm: ThreatModel, t: int,
                            out=None, telemetry=NULL_SESSION):
    """One SplitFed round's payload, consuming the numpy RNG and the key
    stream in the sequential loop's order (cluster-major batch sampling; one
    key split per client, no per-cluster sub-stream).  SplitFed sampling
    never depends on the previous round's selection, so the RoundFeeder can
    run this at any depth — no phase-boundary fallback.  ``out`` and
    ``telemetry`` as in :func:`assemble_round`.  Returns (advanced_key,
    (xs, ys, avec, keys))."""
    return _assemble_with(splitfed_keys, rng, key, data, clusters, pcfg, tm,
                          t, out, telemetry)


def splitfed_round_batched(module: SplitModule, theta, clusters, data: ClientData,
                           pcfg: ProtocolConfig, tm: ThreatModel, t: int,
                           rng: np.random.Generator,
                           key: jax.Array, x0, y0, placement: str = "vmap",
                           prefetched=None, with_stats: bool = False,
                           telemetry=None
                           ) -> Tuple[jax.Array, List[Dict[str, Any]]]:
    """Batched SplitFed round through the placement-aware RoundRunner (the
    FedAvg combine hook makes the cluster model the mean of its clients),
    selection left to the caller — the host reference path.
    ``prefetched`` carries a payload pre-assembled by the RoundFeeder — the
    feeder thread already consumed the RNG/key streams in this order."""
    tel = NULL_SESSION if telemetry is None else telemetry
    if prefetched is None:
        with tel.span("round.assemble", round=t):
            key, prefetched = assemble_splitfed_round(rng, key, data,
                                                      clusters, pcfg, tm, t,
                                                      telemetry=tel)
    xs, ys, avec, keys = prefetched
    with tel.span("round.step", round=t,
                  patch_dw_layers=module.patch_dw_layers) as sp:
        (g_avg, p_avg), aux, vlosses, _ = splitfed_runner(
            module, pcfg.lr, placement, with_stats,
            quant=pcfg.comm.quant).candidates(
            theta, (xs, ys, avec, keys), (x0, y0))
        sp.fence(vlosses)
    stats = np.asarray(aux[1]) if with_stats else None
    vlosses = np.asarray(vlosses)
    results = []
    for r, cluster in enumerate(clusters):
        res = dict(vloss=float(vlosses[r]), cluster=cluster,
                   _stacked=(g_avg, p_avg, None, r))
        if stats is not None:
            res["msg_stats"] = stats[r]
        results.append(res)
    return key, results


def splitfed_round_accept(module: SplitModule, theta, clusters,
                          data: ClientData, pcfg: ProtocolConfig,
                          tm: ThreatModel, t: int, rng: np.random.Generator,
                          key: jax.Array, x0, y0, policy,
                          placement: str = "vmap", prefetched=None,
                          telemetry=None):
    """SplitFed's default batched round: FedAvg per cluster + the policy
    selection cascade in one compiled program, one stacked host fetch.
    Returns ``(key, theta', record)`` like :func:`pigeon_round_accept`
    (``detections`` always 0 and ``accepted`` always True — no handoff
    verify stage)."""
    from ..selection import unpack_fetch
    tel = NULL_SESSION if telemetry is None else telemetry
    if prefetched is None:
        with tel.span("round.assemble", round=t):
            key, prefetched = assemble_splitfed_round(rng, key, data,
                                                      clusters, pcfg, tm, t,
                                                      telemetry=tel)
    runner = splitfed_accept_runner(module, pcfg.lr, placement, policy,
                                    quant=pcfg.comm.quant)
    with tel.span("round.step", round=t,
                  patch_dw_layers=module.patch_dw_layers) as sp:
        theta_next, fetch = runner.accept(theta, prefetched, (x0, y0))
        sp.fence(fetch)
    with tel.span("round.fetch", round=t):
        vlosses, tlosses, selected, detections, accepted = unpack_fetch(
            np.asarray(fetch), len(clusters))
    record = dict(val_losses=[float(v) for v in vlosses],
                  train_losses=[float(v) for v in tlosses],
                  selected=selected, detections=detections, accepted=accepted)
    return key, theta_next, record


# ---------------------------------------------------------------------------
# round-block execution: K host-assembled rounds, one scanned device program
# ---------------------------------------------------------------------------

@jax.jit
def _stack_tree(payloads):
    return jax.tree.map(lambda *ls: jnp.stack(ls), *payloads)


def stack_payloads(payloads):
    """Stack K per-round payload pytrees along a new leading round axis —
    the xs of the RoundRunner's ``lax.scan`` block entries (step i slices
    back exactly round i's payload).  Jitted so the whole pytree stacks in
    ONE dispatch (an eager per-leaf ``jnp.stack`` costs a dispatch per leaf,
    which at small per-round compute eats the fusion win)."""
    return _stack_tree(tuple(payloads))


def assemble_block(rng: np.random.Generator, key: jax.Array, data: ClientData,
                   pcfg: ProtocolConfig, tm: ThreatModel, t0: int, k: int,
                   out=None, telemetry=NULL_SESSION):
    """Host-side payload for a K-round block starting at round ``t0``:
    cluster partitions, stacked mini-batches, derived per-client keys and
    attack state for rounds ``t0 .. t0+k-1``, stacked to a leading K axis.

    Consumes the numpy RNG and the JAX key stream in EXACTLY the synchronous
    per-round order — for each round in turn: the cluster partition draw,
    then that round's :func:`assemble_round` — so after assembly both streams
    sit precisely where the per-round loop would leave them at the end of
    round ``t0+k-1``.  (The fused acceptance path splits no keys after
    assembly, which is why a single post-block stream snapshot gives the
    same crash-atomic resume semantics as per-round checkpoints.)

    Returns ``(advanced_key, clusters_k, block_inputs)`` where ``clusters_k``
    is the K per-round cluster partitions (the host replay needs them for
    History/honesty/CommMeter bookkeeping).

    ``out`` — one lane's view of a job pool's ``(J, K, R, M_bar, E, B)``
    index buffer — takes the drawn indices and returns the SMALL leaves raw
    (a list of K ``(avec, keys)`` payloads, no stacking): the caller owns
    the put, the gather and the stack, so a J-lane pool block pays one put
    and one gather instead of J."""
    return _assemble_block_with(assemble_round, rng, key, data, pcfg, tm,
                                t0, k, out=out, telemetry=telemetry)


def assemble_splitfed_block(rng: np.random.Generator, key: jax.Array,
                            data: ClientData, pcfg: ProtocolConfig,
                            tm: ThreatModel, t0: int, k: int,
                            telemetry=NULL_SESSION):
    """SplitFed variant of :func:`assemble_block` (cluster-major batch
    sampling, one key split per client — see
    :func:`assemble_splitfed_round`)."""
    return _assemble_block_with(assemble_splitfed_round, rng, key, data,
                                pcfg, tm, t0, k, telemetry=telemetry)


def _assemble_block_with(assemble_one, rng: np.random.Generator,
                         key: jax.Array, data: ClientData,
                         pcfg: ProtocolConfig, tm: ThreatModel,
                         t0: int, k: int, out=None, telemetry=NULL_SESSION):
    """Shared K-round assembly: the indices of all K rounds are drawn into
    ONE (K, R, M_bar, E, B) buffer (per-round ``out=`` views of it), so the
    block pays a single put and a single device gather; the small leaves
    (AttackVec state, per-client keys) are stacked on device.

    With ``out`` the caller provides the index buffer and gets the small
    leaves back raw (list of K ``(avec, keys)``; see
    :func:`assemble_block`)."""
    m_bar = pcfg.M // pcfg.R
    idx_k = (np.empty((k, pcfg.R, m_bar, pcfg.E, pcfg.B), dtype=np.int32)
             if out is None else out)
    clusters_k, small = [], []
    for i in range(k):
        clusters = make_clusters(rng, pcfg.M, pcfg.R)
        key, small_i = assemble_one(rng, key, data, clusters, pcfg, tm,
                                    t0 + i, out=idx_k[i], telemetry=telemetry)
        clusters_k.append(clusters)
        small.append(small_i)
    if out is not None:
        return key, clusters_k, small
    avec_k, keys_k = stack_payloads(small)
    xs_k, ys_k = gather_batches(data, idx_k, telemetry, round=t0, k=k)
    return key, clusters_k, (xs_k, ys_k, avec_k, keys_k)


def pigeon_block_accept(module: SplitModule, theta, clusters_k,
                        pcfg: ProtocolConfig, tm: ThreatModel, t0: int,
                        block_inputs, x0, y0, policy, placement: str = "vmap",
                        telemetry=None):
    """K consecutive fused acceptance rounds as ONE compiled ``lax.scan``
    program with a single stacked ``(K, 2R+3)`` host fetch — the round-block
    variant of :func:`pigeon_round_accept`.  Returns ``(theta_next,
    records)`` with one per-round record dict (the History fields:
    val_losses / train_losses / selected / detections / accepted) per
    scanned round.

    Unlike the per-round path, NO CommMeter accounting happens here: the
    driver replays client turns, validation pushes, tamper re-checks and the
    winner broadcast per round from ``records`` + ``clusters_k`` (the counts
    are analytic in the record fields, so the replay is bit-identical to
    per-round metering by construction).  Same precondition as the per-round
    accept: no param-tamper threat models (those are host-sequenced and pin
    ``block=1``)."""
    from ..selection import unpack_block_fetch
    assert not tm.has_param_tamper, \
        "param-tamper threat models must use the host selection cascade"
    tel = NULL_SESSION if telemetry is None else telemetry
    runner = protocol_accept_runner(module, pcfg.lr, placement, policy,
                                    pcfg.tamper_check, pcfg.tamper_tol,
                                    quant=pcfg.comm.quant)
    k = len(clusters_k)
    with tel.span("block.step", round=t0, k=k,
                  patch_dw_layers=module.patch_dw_layers) as sp:
        theta_next, fetches = runner.accept_block(theta, block_inputs,
                                                  (x0, y0))
        sp.fence(fetches)
    with tel.span("block.fetch", round=t0, k=k):
        fetched = np.asarray(fetches)          # the block's ONE host sync
    records = []
    for vlosses, tlosses, selected, detections, accepted in \
            unpack_block_fetch(fetched, len(clusters_k[0])):
        records.append(dict(val_losses=[float(v) for v in vlosses],
                            train_losses=[float(v) for v in tlosses],
                            selected=selected, detections=detections,
                            accepted=accepted))
    return theta_next, records


def splitfed_block_accept(module: SplitModule, theta, clusters_k,
                          pcfg: ProtocolConfig, t0: int, block_inputs, x0, y0,
                          policy, placement: str = "vmap", telemetry=None):
    """SplitFed round-block: K FedAvg + selection-cascade rounds as one
    scanned program, one stacked fetch — the block variant of
    :func:`splitfed_round_accept` (verify stage off: no chained handoff).
    Accounting is the driver's analytic per-round replay
    (``account_splitfed_round``), exactly as in per-round mode."""
    from ..selection import unpack_block_fetch
    tel = NULL_SESSION if telemetry is None else telemetry
    runner = splitfed_accept_runner(module, pcfg.lr, placement, policy,
                                    quant=pcfg.comm.quant)
    k = len(clusters_k)
    with tel.span("block.step", round=t0, k=k,
                  patch_dw_layers=module.patch_dw_layers) as sp:
        theta_next, fetches = runner.accept_block(theta, block_inputs,
                                                  (x0, y0))
        sp.fence(fetches)
    with tel.span("block.fetch", round=t0, k=k):
        fetched = np.asarray(fetches)
    records = []
    for vlosses, tlosses, selected, detections, accepted in \
            unpack_block_fetch(fetched, len(clusters_k[0])):
        records.append(dict(val_losses=[float(v) for v in vlosses],
                            train_losses=[float(v) for v in tlosses],
                            selected=selected, detections=detections,
                            accepted=accepted))
    return theta_next, records


# ---------------------------------------------------------------------------
# multi-seed sweep: whole protocol replicas over (seed, cluster)
# ---------------------------------------------------------------------------

def sweep_round(module: SplitModule, lr: float, theta_s, inputs, val,
                placement: str = "vmap", policy=None,
                quant: Optional[str] = None):
    """One global round for S independent protocol replicas through the
    RoundRunner's sweep entry: per seed, the cluster-parallel round + policy
    selection + winner carry, all inside one compiled program.  Under
    ``placement="sharded"`` the S x R replica grid is laid over a 2-D
    ``(seed, pod)`` device mesh (per-seed selection stays on device: the
    cluster-axis feature all-gathers and the winner psum are the only
    collectives).  Returns ``(theta_S, train_aux_SRM, vlosses_SR,
    sels_S)``."""
    with_stats = policy is not None and policy.needs_message_stats
    return protocol_runner(module, lr, placement, with_stats,
                           policy, quant).sweep(theta_s, inputs, val)


@lru_cache(maxsize=None)
def _sweep_count(module: SplitModule):
    """Jitted seed-vmapped correct-prediction count, cached per module.
    Counting on device avoids transferring the full (S, b, classes) logits
    tensor to the host for every evaluation batch; the integer counts are
    the same, so the resulting accuracies are bit-identical."""
    @jax.jit
    def count(gammas, phis, xb, yb):
        logits = jax.vmap(module.predict, in_axes=(0, 0, None))(
            gammas, phis, xb)                              # (S, b, classes)
        return jnp.sum(jnp.argmax(logits, axis=-1) == yb[None],
                       axis=-1, dtype=jnp.int32)           # (S,)
    return count


def evaluate_sweep(module: SplitModule, gammas, phis, x_test: np.ndarray,
                   y_test: np.ndarray, batch: int = 500) -> np.ndarray:
    """Per-seed test accuracy: ``module.predict`` vmapped over the seed axis,
    batched over the test set exactly like ``protocol.evaluate``.  Counts
    accumulate on device; the evaluation's only host transfer is one final
    (S,) int32 vector."""
    count = _sweep_count(module)
    correct = None
    total = 0
    for i in range(0, x_test.shape[0], batch):
        xb = jnp.asarray(x_test[i : i + batch])
        yb = jnp.asarray(y_test[i : i + batch])
        c = count(gammas, phis, xb, yb)
        correct = c if correct is None else correct + c
        total += int(y_test[i : i + batch].shape[0])
    correct = np.asarray(correct)              # the evaluation's one fetch
    return correct / float(total)


def run_pigeon_sweep(module: SplitModule, data: ClientData, pcfg: ProtocolConfig,
                     malicious: Optional[Set[int]] = None, attack: Attack = HONEST,
                     seeds: Sequence[int] = (0, 1, 2),
                     verbose: bool = False, placement: str = "vmap",
                     threat_model: Optional[ThreatModel] = None,
                     selection="argmin",
                     quant: Optional[str] = None,
                     telemetry=None, block: int = 1) -> List[History]:
    """S whole Pigeon-SL replicas (different seeds) advanced in lockstep: one
    compiled call per global round trains S x R clusters and performs the
    per-seed argmin selection on device.  ``placement="vmap"`` runs the
    (seed, cluster) grid as two nested vmaps on one device;
    ``placement="sharded"`` lays it over a 2-D ``(seed, pod)`` device mesh
    (auto-factorised to cover the most devices — see
    :func:`repro.core.runner.sweep_mesh`), with the per-seed argmin still on
    device.

    Selection happens inside the compiled program under the policy named by
    ``selection`` (``repro.selection``; per-seed scores, default argmin), so
    the host-side param-tamper handoff check is not modelled — the sweep
    supports the honest case and every message-level threat model
    (heterogeneous mixtures and schedules included).  Returns one
    ``History`` per seed (CommMeter accounting is analytic and identical
    across seeds).

    ``block > 1`` chains up to ``block`` consecutive global rounds as one
    scanned device program with a single stacked host fetch per block
    (:meth:`repro.core.runner.RoundRunner.sweep_block`); blocks break at
    eval sync rounds (``pcfg.eval_every``) so per-seed evaluation still sees
    every required intermediate state, and the per-round Histories replayed
    from the block fetch are bit-identical to ``block=1``.
    """
    from ..selection import resolve_policy
    from .comm import CommConfig
    from .protocol import check_block
    from .runner import check_placement
    check_placement(placement)
    block = check_block(block, "batched", eval_every=pcfg.eval_every)
    if quant is not None:
        pcfg = dataclasses.replace(pcfg, comm=CommConfig(quant=quant))
    policy = resolve_policy(selection)
    tm = resolve_threat_model(malicious, attack, threat_model)
    if tm.has_param_tamper:
        raise ValueError("run_pigeon_sweep does not model the param-tamper "
                         "handoff check; use run_pigeon(engine=...) per seed")
    seeds = tuple(int(s) for s in seeds)
    rngs = [np.random.default_rng(s) for s in seeds]
    keys, k0s = [], []
    for s in seeds:
        k, k0 = jax.random.split(jax.random.PRNGKey(s))
        keys.append(k)
        k0s.append(k0)
    thetas = jax.vmap(module.init)(jnp.stack(k0s))
    x0, y0 = jnp.asarray(data.x0), jnp.asarray(data.y0)
    d_o = data.x0.shape[0]
    d_cl = _count_params(jax.tree.map(lambda a: a[0], thetas[0]))
    d_c = cut_width(module, jax.tree.map(lambda a: a[0], thetas[0]), data.x0)
    hists = [History() for _ in seeds]
    from ..telemetry import resolve_telemetry
    tel = resolve_telemetry(telemetry, run="sweep", placement=placement,
                            T=pcfg.T, M=pcfg.M, R=pcfg.R, seeds=list(seeds),
                            selection=policy.name)
    resident_data(data, tel)

    if block > 1:
        # Round-block execution: chain K global rounds as one scanned sweep
        # program (RoundRunner.sweep_block) with a single stacked host fetch,
        # then replay the per-seed History records from it.  Per-round
        # assembly order per seed (cluster draw, then batches/keys) is
        # preserved exactly, so the trajectories are bit-identical to
        # block=1.
        from ..data.pipeline import plan_blocks
        runner = protocol_runner(module, pcfg.lr, placement,
                                 policy.needs_message_stats, policy,
                                 pcfg.comm.quant)
        segments = plan_blocks(0, pcfg.T, block,
                               lambda t: (t % pcfg.eval_every == 0
                                          or t == pcfg.T - 1))
        try:
            for t0, k in segments:
                tel.profile_tick(t0)
                with tel.span("block.assemble", round=t0, k=k):
                    clusters_sk, payloads = [], []
                    for i in range(k):
                        clusters_s = [make_clusters(rngs[j], pcfg.M, pcfg.R)
                                      for j in range(len(seeds))]
                        xs, ys, key_rows, avecs = [], [], [], []
                        for j in range(len(seeds)):
                            keys[j], (x_j, y_j, avec_j, krow) = assemble_round(
                                rngs[j], keys[j], data, clusters_s[j], pcfg,
                                tm, t0 + i, telemetry=tel)
                            xs.append(x_j)
                            ys.append(y_j)
                            key_rows.append(krow)
                            avecs.append(avec_j)
                        avec = jax.tree.map(lambda *ls: jnp.stack(ls), *avecs)
                        payloads.append((jnp.stack(xs), jnp.stack(ys), avec,
                                         jnp.stack(key_rows)))
                        clusters_sk.append(clusters_s)
                    block_inputs = stack_payloads(payloads)
                with tel.span("block.step", round=t0, k=k,
                              patch_dw_layers=module.patch_dw_layers) as sp:
                    thetas, (vl_k, tl_k, sels_k) = runner.sweep_block(
                        thetas, block_inputs, (x0, y0))
                    sp.fence(sels_k)
                with tel.span("block.fetch", round=t0, k=k):
                    vl_k = np.asarray(vl_k)      # (K, S, R)
                    tl_k = np.asarray(tl_k)      # (K, S, R)
                    sels_k = np.asarray(sels_k)  # (K, S)
                gammas, phis = thetas
                for i in range(k):
                    t = t0 + i
                    clusters_s = clusters_sk[i]
                    meter = CommMeter()
                    for cluster in clusters_s[0]:
                        for j in range(len(cluster)):
                            account_client_turn(meter, pcfg, d_c, d_cl,
                                                handoff=j < len(cluster) - 1)
                        account_validation(meter, d_o, d_c)
                    if pcfg.tamper_check:
                        account_handoff_recheck(meter, pcfg, d_o, d_c,
                                                visited=1)
                    account_param_transfer(meter, pcfg.R * d_cl)
                    accs = None
                    if t % pcfg.eval_every == 0 or t == pcfg.T - 1:
                        # plan_blocks ends every block at an eval sync round,
                        # so thetas here is exactly the post-round-t state
                        with eval_span(tel, data, t):
                            accs = evaluate_sweep(module, gammas, phis,
                                                  data.x_test, data.y_test,
                                                  pcfg.eval_batch)
                    for j in range(len(seeds)):
                        sel = int(sels_k[i][j])
                        rec = dict(
                            round=t,
                            clusters=clusters_s[j],
                            val_losses=[float(v) for v in vl_k[i][j]],
                            train_losses=[float(v) for v in tl_k[i][j]],
                            selected=sel,
                            selected_honest=cluster_is_honest(
                                clusters_s[j][sel], tm.malicious),
                            honest_cluster_exists=any(
                                cluster_is_honest(c, tm.malicious)
                                for c in clusters_s[j]),
                            comm=dataclasses.asdict(meter),
                        )
                        if accs is not None:
                            rec["test_acc"] = float(accs[j])
                        hists[j].rounds.append(rec)
                        tel.record_round(t, rec, seed=seeds[j])
                    if verbose:
                        acc_str = ("" if accs is None
                                   else " acc=" + "/".join(f"{a:.3f}"
                                                           for a in accs))
                        print(f"[sweep] t={t:3d} sel={sels_k[i].tolist()}"
                              f"{acc_str}")
        finally:
            tel.close()
        return hists

    try:
        for t in range(pcfg.T):
            tel.profile_tick(t)
            with tel.span("round.assemble", round=t):
                clusters_s = [make_clusters(rngs[i], pcfg.M, pcfg.R)
                              for i in range(len(seeds))]
                xs, ys, key_rows, avecs = [], [], [], []
                for i in range(len(seeds)):
                    keys[i], (x_i, y_i, avec_i, krow) = assemble_round(
                        rngs[i], keys[i], data, clusters_s[i], pcfg, tm, t,
                        telemetry=tel)
                    xs.append(x_i)
                    ys.append(y_i)
                    key_rows.append(krow)
                    avecs.append(avec_i)
                avec = jax.tree.map(lambda *ls: jnp.stack(ls), *avecs)
            with tel.span("round.step", round=t,
                          patch_dw_layers=module.patch_dw_layers) as sp:
                thetas, aux, vlosses, sels = sweep_round(
                    module, pcfg.lr, thetas,
                    (jnp.stack(xs), jnp.stack(ys), avec,
                     jnp.stack(key_rows)),
                    (x0, y0), placement, policy, pcfg.comm.quant)
                sp.fence(vlosses)
            gammas, phis = thetas
            tloss_rm = aux[0] if isinstance(aux, tuple) else aux
            tlosses = jnp.mean(tloss_rm, axis=-1)   # (S, R): mean over clients

            meter = CommMeter()
            for cluster in clusters_s[0]:
                for j in range(len(cluster)):
                    account_client_turn(meter, pcfg, d_c, d_cl,
                                        handoff=j < len(cluster) - 1)
                account_validation(meter, d_o, d_c)
            if pcfg.tamper_check:
                # run_pigeon inspects exactly one candidate per round in the
                # honest/message-attack cases the sweep supports: the
                # next-round first clients' re-transmission of its handoff
                # activations.
                account_handoff_recheck(meter, pcfg, d_o, d_c, visited=1)
            account_param_transfer(meter, pcfg.R * d_cl)

            vlosses = np.asarray(vlosses)
            sels = np.asarray(sels)
            tlosses = np.asarray(tlosses)
            accs = None
            if t % pcfg.eval_every == 0 or t == pcfg.T - 1:
                with eval_span(tel, data, t):
                    accs = evaluate_sweep(module, gammas, phis, data.x_test,
                                          data.y_test, pcfg.eval_batch)
            for i in range(len(seeds)):
                sel = int(sels[i])
                rec = dict(
                    round=t,
                    clusters=clusters_s[i],
                    val_losses=[float(v) for v in vlosses[i]],
                    train_losses=[float(v) for v in tlosses[i]],
                    selected=sel,
                    selected_honest=cluster_is_honest(clusters_s[i][sel],
                                                      tm.malicious),
                    honest_cluster_exists=any(
                        cluster_is_honest(c, tm.malicious)
                        for c in clusters_s[i]),
                    comm=dataclasses.asdict(meter),
                )
                if accs is not None:
                    rec["test_acc"] = float(accs[i])
                hists[i].rounds.append(rec)
                tel.record_round(t, rec, seed=seeds[i])
            if verbose:
                acc_str = ("" if accs is None
                           else " acc=" + "/".join(f"{a:.3f}" for a in accs))
                print(f"[sweep] t={t:3d} sel={sels.tolist()}{acc_str}")
    finally:
        tel.close()
    return hists
