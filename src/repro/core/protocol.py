"""Protocol drivers: Pigeon-SL (Algorithm 1), Pigeon-SL+, vanilla SL and the
clustered SplitFed baseline of Section V.

Every driver returns a ``History`` whose per-round records include test
accuracy, per-cluster validation losses, the selected cluster, whether that
cluster was honest, tamper-detection events, and message-count accounting
(floats transmitted, client fwd+bwd passes) so that Table I's complexity
formulas can be validated against the measured counts.
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..adversary import ThreatModel, resolve_threat_model
from ..selection import resolve_policy, select_host
from ..telemetry import NULL_SESSION, Telemetry, resolve_telemetry
from .attacks import Attack, HONEST
from .clustering import cluster_is_honest, make_clusters
from .comm import CommConfig, FLOAT_BYTES, message_bytes
from .split import SplitModule, client_update, client_update_stats
from .validation import validation_loss

Pytree = Any


@dataclasses.dataclass(frozen=True)
class ProtocolConfig:
    M: int                    # total clients
    N: int = 0                # tolerated malicious clients; R = N + 1
    T: int = 50               # global rounds
    E: int = 10               # mini-batch updates per client turn
    B: int = 64               # mini-batch size
    lr: float = 1e-3
    seed: int = 0
    tamper_check: bool = True
    tamper_tol: float = 1e-4
    eval_every: int = 1
    eval_batch: int = 500
    comm: CommConfig = CommConfig()
    # Observability config (spans / sinks / profiler — see repro.telemetry);
    # None = off.  A driver-level ``telemetry=`` kwarg takes precedence.
    telemetry: Optional[Telemetry] = None

    @property
    def R(self) -> int:
        return self.N + 1

    @property
    def quant(self) -> Optional[str]:
        """Cut-layer wire format (``None`` = f32) — see :mod:`core.comm`."""
        return self.comm.quant


@dataclasses.dataclass
class ClientData:
    """Per-client local shards + the shared/reference and test sets."""
    x: np.ndarray             # (M, D_m, ...)
    y: np.ndarray             # (M, D_m)
    x0: np.ndarray            # (D_o, ...) shared validation inputs
    y0: np.ndarray            # (D_o,)
    x_test: np.ndarray
    y_test: np.ndarray
    # the batched engine's device copy of x/y, put once per object
    # (``engine.resident_data``)
    _resident: Any = dataclasses.field(default=None, init=False, repr=False,
                                       compare=False)


@dataclasses.dataclass
class CommMeter:
    """Message accounting in float-counts (Table I units: d_c, d_CL) and in
    wire bytes.  Float counts are format-independent — they count message
    *elements*, so Table I's formulas stay valid under any ``CommConfig``;
    the ``*_bytes`` fields measure the actual wire (quantized cut-layer
    exchanges charge ``itemsize*elements + 4 bytes/row``; defense-critical
    validation pushes and parameter handoffs always travel f32)."""
    activation_floats: int = 0      # cut-layer activations, both directions
    gradient_floats: int = 0        # cut-layer gradients
    param_floats: int = 0           # client-side parameter handoffs (d_CL)
    validation_floats: int = 0      # shared-set activations for validation/check
    client_passes: int = 0          # forward(+backward) passes through gamma (F_CL)
    activation_bytes: int = 0       # wire bytes of the uplink cut activations
    gradient_bytes: int = 0         # wire bytes of the downlink cut gradients
    param_bytes: int = 0            # wire bytes of parameter handoffs (f32)
    validation_bytes: int = 0       # wire bytes of validation pushes (f32)

    def total_comm(self) -> int:
        return (self.activation_floats + self.gradient_floats
                + self.param_floats + self.validation_floats)

    def total_bytes(self) -> int:
        return (self.activation_bytes + self.gradient_bytes
                + self.param_bytes + self.validation_bytes)

    def exchange_bytes(self) -> int:
        """Wire bytes of the two quantizable cut-layer message streams."""
        return self.activation_bytes + self.gradient_bytes


@dataclasses.dataclass
class History:
    rounds: List[Dict[str, Any]] = dataclasses.field(default_factory=list)

    def series(self, key):
        return [r.get(key) for r in self.rounds]


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _count_params(tree) -> int:
    return int(sum(np.prod(l.shape) for l in jax.tree.leaves(tree)))


def sample_batch_idx(rng: np.random.Generator, n: int, e: int, b: int) -> np.ndarray:
    """(E, B) mini-batch indices for one client turn.  The single batch-
    sampling primitive shared by both engines: the sequential/batched
    equivalence contract requires them to consume the numpy RNG identically,
    so any change to the sampling scheme must go through here."""
    return rng.integers(0, n, size=(e, b))


def _sample_batches(rng: np.random.Generator, x: np.ndarray, y: np.ndarray,
                    e: int, b: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    idx = sample_batch_idx(rng, x.shape[0], e, b)
    return jnp.asarray(x[idx]), jnp.asarray(y[idx])


ENGINES = ("sequential", "batched")


def _check_engine(engine: str, placement: str = "vmap",
                  prefetch: int = 0) -> None:
    if engine not in ENGINES:
        raise ValueError(f"engine={engine!r} must be one of {ENGINES}")
    from .runner import check_placement
    check_placement(placement)
    if placement != "vmap" and engine != "batched":
        raise ValueError(f"placement={placement!r} requires engine='batched' "
                         f"(the sequential oracle has no cluster axis to place)")
    if prefetch > 0 and engine != "batched":
        raise ValueError(f"prefetch={prefetch} requires engine='batched' "
                         f"(the sequential oracle assembles per client turn)")


def check_block(block: int, engine: str = "batched", *, plus: bool = False,
                has_param_tamper: bool = False,
                force_host_selection: bool = False, eval_every: int = 1,
                checkpoint_path: Optional[str] = None,
                checkpoint_every: int = 1) -> int:
    """Validate the round-block knobs up front (mirroring
    :func:`_check_engine`) and return the *effective* block size.

    Impossible combinations raise; the forced-per-round cases — Pigeon-SL+
    sub-round sampling and param-tamper handoff key splits, where the data
    for round t+1 depends on round t's selection — warn and degrade to
    ``block=1`` so callers can thread ``block=`` unconditionally, exactly as
    ``prefetch`` degrades to synchronous assembly at the same phase
    boundaries.  Sync-cadence degradations (``eval_every=1`` /
    ``checkpoint_every=1`` make every round a host sync point, so blocks
    shrink back to single rounds) keep the requested block but warn, since
    they silently erase the fusion win."""
    import warnings
    if block < 1:
        raise ValueError(f"block={block} must be >= 1")
    if checkpoint_every < 1:
        raise ValueError(f"checkpoint_every={checkpoint_every} must be >= 1")
    if block == 1:
        return 1
    if engine != "batched":
        raise ValueError(
            f"block={block} requires engine='batched' (the sequential "
            f"oracle dispatches per client turn and cannot scan rounds)")
    if plus:
        warnings.warn(
            f"block={block} forced to 1: Pigeon-SL+ sub-rounds sample the "
            f"previous round's selected cluster, so round t+1's host "
            f"assembly cannot run before round t's selection", stacklevel=3)
        return 1
    if has_param_tamper:
        warnings.warn(
            f"block={block} forced to 1: param-tamper threat models split "
            f"the protocol key per visited candidate during host-side "
            f"selection, which is inherently per-round", stacklevel=3)
        return 1
    if force_host_selection:
        warnings.warn(
            f"block={block} forced to 1: the host-side reference cascade "
            f"needs every round's candidates on the host", stacklevel=3)
        return 1
    if eval_every == 1:
        warnings.warn(
            f"block={block} degrades to per-round execution: eval_every=1 "
            f"makes every round an eval sync point — raise pcfg.eval_every "
            f"to let rounds fuse", stacklevel=3)
    elif checkpoint_path is not None and checkpoint_every == 1:
        warnings.warn(
            f"block={block} degrades to per-round execution: "
            f"checkpoint_every=1 checkpoints every round — raise "
            f"checkpoint_every to let rounds fuse", stacklevel=3)
    return block


def account_client_turn(meter: CommMeter, pcfg: ProtocolConfig, d_c: int,
                        d_cl: int, handoff: bool) -> None:
    """Table I accounting for one client's turn (E batches of B samples:
    activations up, cut gradients down, plus the intra-cluster parameter
    handoff).  Shared by the sequential and batched engines so their
    CommMeter counts are bit-identical by construction.  Byte charges read
    ``pcfg.comm.quant``: each of the E batches is one (B, d_c) quantized
    message per direction (1 byte/element + one f32 scale per sample);
    handoffs stay f32."""
    quant = pcfg.comm.quant
    n_samples = pcfg.E * pcfg.B
    meter.client_passes += n_samples
    meter.activation_floats += n_samples * d_c
    meter.gradient_floats += n_samples * d_c
    meter.activation_bytes += pcfg.E * message_bytes(quant, pcfg.B, d_c)
    meter.gradient_bytes += pcfg.E * message_bytes(quant, pcfg.B, d_c)
    if handoff:
        account_param_transfer(meter, d_cl)


def account_validation(meter: CommMeter, d_o: int, d_c: int) -> None:
    """One cluster's shared-set validation push (Section III-C) — always f32:
    quantizing the message the tamper check and selection scores read would
    let an attacker hide inside quantization noise."""
    meter.validation_floats += d_o * d_c
    meter.validation_bytes += d_o * d_c * FLOAT_BYTES
    meter.client_passes += d_o


def account_param_transfer(meter: CommMeter, n_floats: int) -> None:
    """A parameter transfer of ``n_floats`` f32 values (handoffs, broadcasts,
    FedAvg uploads) — the single site that keeps ``param_floats`` and
    ``param_bytes`` consistent."""
    meter.param_floats += n_floats
    meter.param_bytes += n_floats * FLOAT_BYTES


def account_handoff_recheck(meter: CommMeter, pcfg: ProtocolConfig, d_o: int,
                            d_c: int, visited: int = 1) -> None:
    """Tamper-check replay of the R-candidate handoff chain for ``visited``
    inspected candidates (shared-set push per cluster, f32)."""
    meter.validation_floats += visited * pcfg.R * d_o * d_c
    meter.validation_bytes += visited * pcfg.R * d_o * d_c * FLOAT_BYTES
    meter.client_passes += visited * pcfg.R * d_o


def account_splitfed_round(meter: CommMeter, pcfg: ProtocolConfig, clusters,
                           d_o: int, d_c: int, d_cl: int) -> None:
    """One SplitFed round's message accounting — analytic, so it is
    engine-independent (bit-identical across sequential/batched/fused by
    construction): every client runs its E x B exchanges in parallel from the
    same incoming params and uploads its client-side params for the FedAvg
    combine (``handoff=True``); each cluster pushes one shared-set
    validation; the selected cluster's client params broadcast to all M
    clients for the next round."""
    for cluster in clusters:
        for _ in cluster:
            account_client_turn(meter, pcfg, d_c, d_cl, handoff=True)
        account_validation(meter, d_o, d_c)
    n_clients = sum(len(c) for c in clusters)
    account_param_transfer(meter, n_clients * d_cl)


def res_params(res: Dict[str, Any]) -> Tuple[Pytree, Pytree]:
    """(gamma, phi) of one cluster result.  The batched engine returns its R
    candidates as views into stacked arrays and only the clusters the
    selection loop actually inspects (usually one) get sliced out — R x
    n_leaves tiny slice dispatches per round would otherwise erase much of
    the batching win."""
    if "gamma" not in res:
        gs, ps, _, r = res["_stacked"]
        res["gamma"] = jax.tree.map(lambda a: a[r], gs)
        res["phi"] = jax.tree.map(lambda a: a[r], ps)
    return res["gamma"], res["phi"]


def res_vacts(res: Dict[str, Any]):
    """The cluster's validation-time cut activations (for the handoff check)."""
    if "vacts" not in res:
        _, _, vacts, r = res["_stacked"]
        res["vacts"] = vacts[r]
    return res["vacts"]


@lru_cache(maxsize=None)
def _eval_count_fn(module: SplitModule):
    """Jitted predict-and-count-correct reduction: each eval batch is one
    device op returning a single int32, instead of a full logits transfer
    followed by a host argmax.  Covers both the classifier (B, C) and LM
    (B, S, V) logit layouts — argmax over the trailing class axis, summed
    over every remaining label position."""

    @jax.jit
    def count(gamma, phi, xb, yb):
        logits = module.predict(gamma, phi, xb)
        return jnp.sum(jnp.argmax(logits, axis=-1) == yb, dtype=jnp.int32)

    return count


def eval_span(tel, data: ClientData, t: int, **attrs):
    """The ``round.eval`` span of round ``t``.  It carries, as
    ``h2d_bytes``, the bytes :func:`evaluate` copies to the device: the whole
    test set."""
    return tel.span("round.eval", round=t,
                    h2d_bytes=data.x_test.nbytes + data.y_test.nbytes,
                    **attrs)


def evaluate(module: SplitModule, gamma, phi, x_test: np.ndarray, y_test: np.ndarray,
             batch: int = 500) -> float:
    if x_test.shape[0] == 0:
        return 0.0      # empty test set: zero correct out of zero, not a crash
    count = _eval_count_fn(module)
    correct = None
    total = 0
    for i in range(0, x_test.shape[0], batch):
        xb = jnp.asarray(x_test[i : i + batch])
        yb = jnp.asarray(y_test[i : i + batch])
        c = count(gamma, phi, xb, yb)
        correct = c if correct is None else correct + c   # stays on device
        total += int(np.prod(y_test[i : i + batch].shape))
    return float(correct) / float(total)                  # one final sync


# ---------------------------------------------------------------------------
# cluster-wise vanilla-SL training pass (lines 3-20 of Algorithm 1)
# ---------------------------------------------------------------------------

def train_cluster(module: SplitModule, gamma, phi, cluster: Sequence[int],
                  data: ClientData, pcfg: ProtocolConfig, tm: ThreatModel,
                  t: int, rng: np.random.Generator, key: jax.Array,
                  meter: CommMeter, d_c: int, collect_stats: bool = False):
    """One cluster's within-cluster client chain.  With ``collect_stats``
    additionally returns the (M_bar, S) per-client transmitted-message
    statistics (``core.split.message_stats``) the anomaly-scoring selection
    policies read; the parameter/loss arithmetic is identical either way."""
    d_cl = _count_params(gamma)
    losses = []
    stats = []
    for j, client in enumerate(cluster):
        xs, ys = _sample_batches(rng, data.x[client], data.y[client], pcfg.E, pcfg.B)
        key, sub = jax.random.split(key)
        a = tm.attack_for(client, t)
        if collect_stats:
            gamma, phi, loss, st = client_update_stats(module, a, gamma, phi,
                                                       (xs, ys), pcfg.lr, sub,
                                                       quant=pcfg.comm.quant)
            stats.append(np.asarray(st))
        else:
            gamma, phi, loss = client_update(module, a, gamma, phi, (xs, ys),
                                             pcfg.lr, sub,
                                             quant=pcfg.comm.quant)
        losses.append(float(loss))
        account_client_turn(meter, pcfg, d_c, d_cl, handoff=j < len(cluster) - 1)
    if collect_stats:
        return gamma, phi, float(np.mean(losses)), np.stack(stats)
    return gamma, phi, float(np.mean(losses))


def cut_width(module: SplitModule, gamma, x0) -> int:
    """d_c: per-sample width of the cut-layer activation message (computed
    shape-only via eval_shape — no allocation)."""
    shp = jax.eval_shape(module.client_forward, gamma, jnp.asarray(x0[:1]))
    return int(np.prod(shp.shape[1:]))


# ---------------------------------------------------------------------------
# Pigeon-SL / Pigeon-SL+
# ---------------------------------------------------------------------------

def _train_round(module: SplitModule, theta, clusters, data: ClientData,
                 pcfg: ProtocolConfig, tm: ThreatModel, t: int,
                 rng: np.random.Generator, key: jax.Array, meter: CommMeter,
                 d_c: int, x0, y0, engine: str, placement: str = "vmap",
                 prefetched=None, with_stats: bool = False, telemetry=None):
    """Train all R clusters of round t from the same theta^t.  Returns
    (key', results) where results[r] holds gamma/phi/vloss/vacts/cluster/
    train_loss for cluster r.  Both engines consume the numpy RNG and the JAX
    key stream in the same order, so they are swappable mid-trajectory."""
    tel = NULL_SESSION if telemetry is None else telemetry
    if engine == "batched":
        from .engine import train_round_batched
        return train_round_batched(module, theta, clusters, data, pcfg,
                                   tm, t, rng, key, meter, d_c, x0, y0,
                                   placement=placement, prefetched=prefetched,
                                   with_stats=with_stats, telemetry=tel)
    results = []
    with tel.span("round.step", round=t):
        for cluster in clusters:
            key, sub = jax.random.split(key)
            out = train_cluster(module, theta[0], theta[1], cluster, data,
                                pcfg, tm, t, rng, sub, meter, d_c,
                                collect_stats=with_stats)
            g, p, train_loss = out[:3]
            vloss, vacts = validation_loss(module, g, p, x0, y0)
            res = dict(gamma=g, phi=p, vloss=float(vloss), vacts=vacts,
                       cluster=cluster, train_loss=train_loss)
            if with_stats:
                res["msg_stats"] = out[3]
            results.append(res)
    return key, results


def run_pigeon(module: SplitModule, data: ClientData, pcfg: ProtocolConfig,
               malicious: Optional[Set[int]] = None, attack: Attack = HONEST,
               plus: bool = False, verbose: bool = False,
               checkpoint_path: Optional[str] = None, resume: bool = False,
               engine: str = "sequential", placement: str = "vmap",
               prefetch: int = 0, block: int = 1, checkpoint_every: int = 1,
               threat_model: Optional[ThreatModel] = None,
               selection="argmin", quant: Optional[str] = None,
               telemetry=None,
               _force_host_selection: bool = False) -> History:
    """Pigeon-SL (Algorithm 1).  Execution knobs beyond the paper:

    * ``telemetry`` — a :class:`repro.telemetry.Telemetry` config (or an
      already-open session, which the driver borrows without closing):
      phase spans, per-round metric events, JSONL/console/custom sinks and
      opt-in profiler windows.  Overrides ``pcfg.telemetry``.  Telemetry is
      a strict no-op on the math — it consumes no RNG and adds no
      device→host fetches — so the History is bit-identical with it on or
      off.  ``verbose=True`` is a back-compat alias for the console sink
      (one uniform per-round line).

    * ``quant`` — cut-layer wire format shorthand (``"int8"`` /
      ``"fp8_e4m3"``; ``None`` keeps ``pcfg.comm``): overrides the
      ``ProtocolConfig.comm`` transport config for this run.  See
      :mod:`repro.core.comm` for what is (and is not) quantized.

    * ``engine`` — ``"sequential"`` (reference oracle) or ``"batched"`` (one
      compiled program per round via the RoundRunner).  For MANY concurrent
      runs of compatible specs, :func:`repro.core.jobs.run_job_pool`
      megabatches them onto a shared job-lane program (one dispatch and one
      stacked fetch per pool block across all jobs) with each job's History
      bit-identical to its solo ``run_pigeon`` — this driver stays the
      single-job reference path the pool is pinned against.
    * ``selection`` — a registered :mod:`repro.selection` policy name
      (``"argmin"`` / ``"median_of_means"`` / ``"loss_plus_distance"`` /
      ``"trimmed"``) or a policy instance.  The default ``"argmin"`` is the
      paper's rule and reproduces the pre-subsystem trajectories
      bit-for-bit.  Under the batched engine the whole acceptance cascade
      (score -> rank -> handoff verify -> commit) is compiled into the round
      program with a single stacked host fetch per round; the host-side
      reference cascade (``repro.selection.select_host``) runs for the
      sequential oracle and for param-tamper threat models, whose handoff
      tampering consumes the protocol key per visited candidate.
      ``_force_host_selection`` pins the batched engine to the host cascade
      (the equivalence suite's oracle knob).
    * ``placement`` — batched engine only: ``"vmap"`` (cluster axis vmapped
      on one device) or ``"sharded"`` (cluster axis laid over a device mesh).
    * ``prefetch`` — batched engine only: double-buffer round assembly
      (index draw, index put, device gather, key derivation) ``prefetch``
      rounds ahead on a background thread (``data/pipeline.py::RoundFeeder``).
      The RNG/key consumption order is preserved exactly, so the trajectory
      is bit-identical to ``prefetch=0``.  The feeder bounds its depth to
      zero — synchronous assembly — whenever sampling depends on the previous
      round's outcome: Pigeon-SL+ sub-rounds sample the *selected* cluster,
      and param-tamper threat models consume the key stream at selection
      time, so both fall back transparently.
    * ``block`` — batched engine only: chain up to ``block`` consecutive
      rounds as ONE compiled ``lax.scan`` program with a single stacked
      ``(K, 2R+3)`` host fetch per block, from which per-round ``History``,
      telemetry round events and ``CommMeter`` deltas are replayed
      bit-identically to ``block=1``.  Host-side K-round assembly preserves
      the per-round RNG/key order exactly (``engine.assemble_block``), so
      the trajectory is unchanged.  Blocks break at *sync rounds* — eval
      rounds (``pcfg.eval_every``) and checkpoint rounds
      (``checkpoint_every``) — because intermediate thetas never leave the
      device mid-block; they are bounded to 1 (with a warning) for
      Pigeon-SL+ and param-tamper threat models, whose round t+1 data
      depends on round t's selection, exactly as ``prefetch`` falls back.
      See :func:`check_block` for the up-front validation.
    * ``checkpoint_every`` — write a checkpoint after round t only when
      ``(t+1) % checkpoint_every == 0`` (or at the final round).  The
      default 1 keeps the historical every-round cadence; raising it both
      amortises checkpoint I/O and lets round blocks fuse across the
      non-checkpointed rounds (resume restarts from the last checkpointed
      round, re-training at most ``checkpoint_every - 1`` rounds).
    * ``checkpoint_path`` / ``resume`` — per-round checkpoints carry theta
      AND the full randomness-stream state (numpy bit-generator state + the
      protocol key), so a resumed run is *on-stream*: it reproduces the
      uninterrupted trajectory bit-for-bit, under either engine, both
      placements, prefetch on or off, and Pigeon-SL+.  Checkpoint writes are
      atomic (temp file + ``os.replace``, manifest last); a torn/corrupt
      checkpoint is detected and skipped with a warning instead of being
      half-loaded.
    """
    _check_engine(engine, placement, prefetch)
    if quant is not None:
        pcfg = dataclasses.replace(pcfg, comm=CommConfig(quant=quant))
    policy = resolve_policy(selection)
    tm = resolve_threat_model(malicious, attack, threat_model)
    block = check_block(block, engine, plus=plus,
                        has_param_tamper=tm.has_param_tamper,
                        force_host_selection=_force_host_selection,
                        eval_every=pcfg.eval_every,
                        checkpoint_path=checkpoint_path,
                        checkpoint_every=checkpoint_every)
    # The fused on-device cascade covers every message-level threat model;
    # handoff (param-tamper) attacks are applied host-side and split the
    # protocol key per *visited* candidate, so they pin selection to the
    # host reference cascade (exactly like the prefetch depth bound).
    fused_selection = (engine == "batched" and not tm.has_param_tamper
                      and not _force_host_selection)
    rng = np.random.default_rng(pcfg.seed)
    key = jax.random.PRNGKey(pcfg.seed)
    key, k0 = jax.random.split(key)
    gamma0, phi0 = module.init(k0)
    theta = (gamma0, phi0)
    start_round = 0
    if resume and checkpoint_path is not None:
        from ..checkpoint import (CorruptCheckpointError, load_checkpoint,
                                  restore_protocol_state, restore_pytree)
        try:
            _, meta = load_checkpoint(checkpoint_path)
            theta = restore_pytree(checkpoint_path, theta)
            start_round = int(meta.get("round", -1)) + 1
            if "rng_state" in meta:
                # On-stream resume: restore the numpy bit-generator state and
                # the protocol key exactly as they stood after the saved
                # round, so the resumed trajectory (clustering, per-turn
                # batch sampling, per-round/tamper-check key splits) is
                # bit-identical to the uninterrupted run.
                key = restore_protocol_state(rng, key, meta)
            else:
                # Legacy checkpoints (no stream snapshot): replay only the
                # clustering draws.  Off-stream for batch sampling and key
                # splits — kept solely so old checkpoints still load.
                for _ in range(start_round):
                    make_clusters(rng, pcfg.M, pcfg.R)
        except FileNotFoundError:
            start_round = 0
        except CorruptCheckpointError as e:
            import warnings
            warnings.warn(f"ignoring corrupt checkpoint {checkpoint_path!r} "
                          f"({e}); starting from round 0", stacklevel=2)
            start_round = 0
    if start_round >= pcfg.T:
        # The checkpoint already covers the final round: training would be a
        # zero-iteration loop returning an empty History.  Surface the
        # restored state instead of silently discarding it.
        import warnings
        warnings.warn(
            f"resume: checkpoint {checkpoint_path!r} is at round "
            f"{start_round - 1} >= T-1 = {pcfg.T - 1}; nothing left to train "
            f"— returning the restored final state", stacklevel=2)
        hist = History()
        hist.rounds.append(dict(
            round=start_round - 1, resumed_terminal=True,
            test_acc=evaluate(module, theta[0], theta[1], data.x_test,
                              data.y_test, pcfg.eval_batch)))
        return hist
    x0, y0 = jnp.asarray(data.x0), jnp.asarray(data.y0)
    d_o = data.x0.shape[0]
    hist = History()
    d_cl = _count_params(gamma0)
    d_c = cut_width(module, gamma0, data.x0)
    tel = resolve_telemetry(
        telemetry if telemetry is not None else pcfg.telemetry,
        verbose=verbose, run=f"pigeon{'+' if plus else ''}",
        engine=engine, placement=placement, prefetch=prefetch, block=block,
        T=pcfg.T, M=pcfg.M, R=pcfg.R, selection=policy.name,
        fused_selection=fused_selection)
    if engine == "batched":
        from .engine import resident_data
        resident_data(data, tel)       # the one put, before the first round

    def _ckpt_due(t: int) -> bool:
        return checkpoint_path is not None and (
            (t + 1) % checkpoint_every == 0 or t == pcfg.T - 1)

    if block > 1:
        # Round-block execution (check_block guarantees the fused batched
        # path here): K rounds chained on device as one lax.scan with the
        # selection cascade in-carry, ONE stacked host fetch per block, and
        # the per-round History / telemetry / CommMeter records replayed
        # host-side bit-identically to per-round execution.  Blocks end at
        # sync rounds (eval / checkpoint cadence) since intermediate thetas
        # never leave the device; the K-round host assembly runs through the
        # same RoundFeeder (block-indexed) so prefetch still overlaps
        # assembly of block b+1 with device execution of block b.
        from ..data.pipeline import RoundFeeder, plan_blocks
        from .engine import assemble_block, pigeon_block_accept

        def _sync_round(t: int) -> bool:
            return (t % pcfg.eval_every == 0 or t == pcfg.T - 1
                    or _ckpt_due(t))

        segments = plan_blocks(start_round, pcfg.T, block, _sync_round)

        _state = {"key": key}

        def _make_block(b):
            t0, k = segments[b]
            _state["key"], clusters_k, payload = assemble_block(
                rng, _state["key"], data, pcfg, tm, t0, k, telemetry=tel)
            # Stream snapshot for the block-end checkpoint: the fused path
            # splits no keys after assembly, so the post-block-assembly
            # stream state IS the synchronous end-of-round state of the
            # block's last round (same argument as the per-round feeder).
            snap = None
            if checkpoint_path is not None:
                from ..checkpoint import protocol_state_metadata
                snap = protocol_state_metadata(rng, _state["key"])
            return clusters_k, payload, snap

        feeder = RoundFeeder(_make_block, 0, len(segments), depth=prefetch,
                             telemetry=tel)
        try:
            for b, (t0, k) in enumerate(segments):
                tel.profile_tick(t0)
                if prefetch > 0:
                    with tel.span("round.feeder_wait", round=t0,
                                  depth=feeder.qsize()):
                        clusters_k, payload, stream_snap = feeder.get(b)
                else:
                    with tel.span("block.assemble", round=t0, k=k):
                        clusters_k, payload, stream_snap = feeder.get(b)
                theta, records = pigeon_block_accept(
                    module, theta, clusters_k, pcfg, tm, t0, payload,
                    x0, y0, policy, placement, telemetry=tel)
                for i, brec in enumerate(records):
                    t = t0 + i
                    clusters = clusters_k[i]
                    meter = CommMeter()
                    # Bit-identical replay of the per-round accounting:
                    # client turns + tamper re-checks (pigeon_round_accept's
                    # internal charges) followed by the driver's validation
                    # pushes and the winner broadcast.
                    for cluster in clusters:
                        for j in range(len(cluster)):
                            account_client_turn(meter, pcfg, d_c, d_cl,
                                                handoff=j < len(cluster) - 1)
                    if pcfg.tamper_check:
                        visited = brec["detections"] + (1 if brec["accepted"]
                                                        else 0)
                        account_handoff_recheck(meter, pcfg, d_o, d_c,
                                                visited)
                    for _ in clusters:
                        account_validation(meter, d_o, d_c)
                    if brec["accepted"]:
                        account_param_transfer(meter, pcfg.R * d_cl)
                    sel_cluster = clusters[brec["selected"]]
                    rec = dict(
                        round=t,
                        clusters=clusters,
                        val_losses=brec["val_losses"],
                        train_losses=brec["train_losses"],
                        selected=brec["selected"],
                        accepted=brec["accepted"],
                        selected_honest=cluster_is_honest(sel_cluster,
                                                          tm.malicious),
                        honest_cluster_exists=any(
                            cluster_is_honest(c, tm.malicious)
                            for c in clusters),
                        detections=brec["detections"],
                        comm=dataclasses.asdict(meter),
                    )
                    if t % pcfg.eval_every == 0 or t == pcfg.T - 1:
                        # only reachable at the block's last scanned round:
                        # plan_blocks breaks blocks at eval sync rounds, so
                        # theta is exactly the post-round-t state
                        with eval_span(tel, data, t):
                            rec["test_acc"] = evaluate(
                                module, theta[0], theta[1], data.x_test,
                                data.y_test, pcfg.eval_batch)
                    hist.rounds.append(rec)
                    if _ckpt_due(t):
                        from ..checkpoint import save_checkpoint
                        with tel.span("round.checkpoint", round=t):
                            save_checkpoint(checkpoint_path, theta,
                                            {"round": t, **stream_snap})
                    tel.record_round(t, rec,
                                     feeder_depth=(feeder.qsize()
                                                   if prefetch > 0 else None))
        finally:
            feeder.close()
            tel.close()
        return hist

    # Double-buffered host pipeline: assembly of round t+1 overlaps device
    # execution of round t.  Depth is bounded to zero (synchronous) at the
    # phase boundaries where sampling depends on round t's outcome — the
    # Pigeon-SL+ sub-rounds resample the selected cluster, and param-tamper
    # threat models split the protocol key during selection.
    feeder = None
    if engine == "batched" and prefetch > 0 and not plus \
            and not tm.has_param_tamper:
        from ..data.pipeline import RoundFeeder
        from .engine import assemble_round

        _state = {"key": key}

        def _make_round(t):
            clusters = make_clusters(rng, pcfg.M, pcfg.R)
            _state["key"], payload = assemble_round(
                rng, _state["key"], data, clusters, pcfg, tm, t,
                telemetry=tel)
            # Stream snapshot for the round-t checkpoint: by the time the
            # main loop saves round t, the feeder has already consumed the
            # RNG/key streams for rounds t+1.., so the snapshot must be taken
            # here — right after round t's assembly, which (feeder
            # preconditions: no Pigeon-SL+ sub-rounds, no param-tamper key
            # splits) is exactly the synchronous end-of-round-t state.
            snap = None
            if checkpoint_path is not None:
                from ..checkpoint import protocol_state_metadata
                snap = protocol_state_metadata(rng, _state["key"])
            return clusters, payload, snap

        feeder = RoundFeeder(_make_round, start_round, pcfg.T, depth=prefetch,
                             telemetry=tel)

    try:
        for t in range(start_round, pcfg.T):
            tel.profile_tick(t)
            meter = CommMeter()
            if feeder is not None:
                with tel.span("round.feeder_wait", round=t,
                              depth=feeder.qsize()):
                    clusters, prefetched, stream_snap = feeder.get(t)
            else:
                clusters = make_clusters(rng, pcfg.M, pcfg.R)
                prefetched = None
                stream_snap = None
            if fused_selection:
                # Default batched path: train + validate + the whole
                # score/rank/verify/commit cascade in ONE compiled program;
                # the stacked record fetch is the round's single host sync.
                from .engine import pigeon_round_accept
                key, theta, sel_rec = pigeon_round_accept(
                    module, theta, clusters, data, pcfg, tm, t, rng, key,
                    meter, d_c, x0, y0, policy, placement, prefetched,
                    telemetry=tel)
                selected = sel_rec["selected"]
                accepted = sel_rec["accepted"]
                detection_events = sel_rec["detections"]
                val_losses = sel_rec["val_losses"]
                train_losses = sel_rec["train_losses"]
                sel_cluster = clusters[selected]
            else:
                # Reference path (sequential oracle / param-tamper threat
                # models): all R candidates, then the host-side cascade.
                key, results = _train_round(
                    module, theta, clusters, data, pcfg, tm, t, rng, key,
                    meter, d_c, x0, y0, engine, placement, prefetched,
                    with_stats=policy.needs_message_stats, telemetry=tel)
                with tel.span("round.select", round=t):
                    key, outcome = select_host(policy, module, results,
                                               theta, tm, t, key, pcfg,
                                               meter, x0, y0, d_c)
                theta = outcome.theta
                selected = outcome.selected
                accepted = outcome.accepted
                detection_events = outcome.detections
                val_losses = [res["vloss"] for res in results]
                train_losses = [res["train_loss"] for res in results]
                sel_cluster = results[selected]["cluster"]
            for _ in clusters:
                account_validation(meter, d_o, d_c)
            if accepted:
                # broadcast to next first clients (no broadcast happens when
                # every cluster failed the tamper check and theta^t is kept)
                account_param_transfer(meter, pcfg.R * d_cl)

            # Pigeon-SL+: R-1 extra sub-rounds on the selected cluster —
            # only when the round was accepted: a rejected round keeps
            # theta^t, and re-training the (tamper-flagged) selected cluster
            # from it would hand a detected attacker R-1 free extra turns.
            if plus and accepted:
                with tel.span("round.subrounds", round=t, n=pcfg.R - 1):
                    for _ in range(pcfg.R - 1):
                        if engine == "batched":
                            from .engine import train_cluster_batched
                            key, g, p, _ = train_cluster_batched(
                                module, theta, sel_cluster, data, pcfg, tm,
                                t, rng, key, meter, d_c, telemetry=tel)
                        else:
                            key, sub = jax.random.split(key)
                            g, p, _ = train_cluster(module, theta[0],
                                                    theta[1], sel_cluster,
                                                    data, pcfg, tm, t, rng,
                                                    sub, meter, d_c)
                        theta = (g, p)
                        # subround handoff to the 1st client
                        account_param_transfer(meter, _count_params(g))

            rec = dict(
                round=t,
                clusters=clusters,
                val_losses=val_losses,
                train_losses=train_losses,
                selected=selected,
                accepted=accepted,
                selected_honest=cluster_is_honest(sel_cluster, tm.malicious),
                honest_cluster_exists=any(cluster_is_honest(c, tm.malicious)
                                          for c in clusters),
                detections=detection_events,
                comm=dataclasses.asdict(meter),
            )
            if t % pcfg.eval_every == 0 or t == pcfg.T - 1:
                with eval_span(tel, data, t):
                    rec["test_acc"] = evaluate(module, theta[0], theta[1],
                                               data.x_test, data.y_test,
                                               pcfg.eval_batch)
            hist.rounds.append(rec)
            if _ckpt_due(t):
                from ..checkpoint import protocol_state_metadata, save_checkpoint
                state = (stream_snap if stream_snap is not None
                         else protocol_state_metadata(rng, key))
                with tel.span("round.checkpoint", round=t):
                    save_checkpoint(checkpoint_path, theta,
                                    {"round": t, **state})
            tel.record_round(t, rec,
                             feeder_depth=(feeder.qsize()
                                           if feeder is not None else None))
    finally:
        if feeder is not None:
            feeder.close()
        tel.close()
    return hist


def run_pigeon_plus(module: SplitModule, data: ClientData, pcfg: ProtocolConfig,
                    malicious: Optional[Set[int]] = None, attack: Attack = HONEST,
                    verbose: bool = False, checkpoint_path: Optional[str] = None,
                    resume: bool = False, engine: str = "sequential",
                    placement: str = "vmap", prefetch: int = 0,
                    block: int = 1, checkpoint_every: int = 1,
                    threat_model: Optional[ThreatModel] = None,
                    selection="argmin", quant: Optional[str] = None,
                    telemetry=None) -> History:
    """Pigeon-SL+ (throughput-matched variant): ``run_pigeon`` with the R-1
    extra selected-cluster sub-rounds enabled.  ``prefetch`` and ``block``
    are accepted for API symmetry but bounded to synchronous per-round
    execution — the sub-rounds sample the selected cluster, so round t+1's
    host work cannot start (and no round may chain on device) before round
    t's selection."""
    return run_pigeon(module, data, pcfg, malicious, attack, plus=True,
                      verbose=verbose, checkpoint_path=checkpoint_path,
                      resume=resume, engine=engine, placement=placement,
                      prefetch=prefetch, block=block,
                      checkpoint_every=checkpoint_every,
                      threat_model=threat_model,
                      selection=selection, quant=quant, telemetry=telemetry)


# ---------------------------------------------------------------------------
# vanilla SL (the paper's baseline)
# ---------------------------------------------------------------------------

def run_vanilla_sl(module: SplitModule, data: ClientData, pcfg: ProtocolConfig,
                   malicious: Optional[Set[int]] = None, attack: Attack = HONEST,
                   verbose: bool = False,
                   threat_model: Optional[ThreatModel] = None,
                   quant: Optional[str] = None, telemetry=None) -> History:
    if quant is not None:
        pcfg = dataclasses.replace(pcfg, comm=CommConfig(quant=quant))
    tm = resolve_threat_model(malicious, attack, threat_model)
    rng = np.random.default_rng(pcfg.seed)
    key = jax.random.PRNGKey(pcfg.seed)
    key, k0 = jax.random.split(key)
    gamma, phi = module.init(k0)
    hist = History()
    d_c = cut_width(module, gamma, data.x0)
    tel = resolve_telemetry(
        telemetry if telemetry is not None else pcfg.telemetry,
        verbose=verbose, run="vanilla", T=pcfg.T, M=pcfg.M)
    try:
        for t in range(pcfg.T):
            tel.profile_tick(t)
            meter = CommMeter()
            order = rng.permutation(pcfg.M).tolist()
            key, sub = jax.random.split(key)
            with tel.span("round.step", round=t):
                gamma, phi, train_loss = train_cluster(
                    module, gamma, phi, order, data, pcfg, tm, t, rng, sub,
                    meter, d_c)
            # hand-off into the next round
            account_param_transfer(meter, _count_params(gamma))
            rec = dict(round=t, train_loss=train_loss,
                       comm=dataclasses.asdict(meter))
            if t % pcfg.eval_every == 0 or t == pcfg.T - 1:
                with eval_span(tel, data, t):
                    rec["test_acc"] = evaluate(module, gamma, phi,
                                               data.x_test, data.y_test,
                                               pcfg.eval_batch)
            hist.rounds.append(rec)
            tel.record_round(t, rec)
    finally:
        tel.close()
    return hist


# ---------------------------------------------------------------------------
# SplitFed baseline (Section V: SFL + our clustering & validation selection)
# ---------------------------------------------------------------------------

def run_splitfed(module: SplitModule, data: ClientData, pcfg: ProtocolConfig,
                 malicious: Optional[Set[int]] = None, attack: Attack = HONEST,
                 verbose: bool = False, engine: str = "sequential",
                 placement: str = "vmap", prefetch: int = 0, block: int = 1,
                 threat_model: Optional[ThreatModel] = None,
                 selection="argmin", quant: Optional[str] = None,
                 telemetry=None,
                 _force_host_selection: bool = False) -> History:
    """Clients inside a cluster train *in parallel* from the same incoming
    params; the cluster model is the FedAvg of its clients.  Cluster
    selection by shared-set validation loss, as the paper's adapted SFL.

    Execution knobs match ``run_pigeon``: the batched engine runs the round
    through the placement-aware RoundRunner (SplitFed's FedAvg is the
    RoundSpec ``combine`` hook), so ``placement="sharded"`` lays the cluster
    axis over a device mesh, and ``prefetch>0`` double-buffers host-side
    round assembly.  ``selection`` plugs any :mod:`repro.selection` policy
    into the round (on the batched engine the selection cascade compiles
    into the round program — SplitFed has no chained handoff, so the verify
    stage stays off).  SplitFed sampling never depends on the previous
    round's selection — there is no tamper-check key split and no sub-round
    — so the feeder runs at full depth under every threat model, and
    ``block > 1`` chains rounds on device under every threat model too
    (blocks break only at eval sync rounds; the per-round History replayed
    from the block fetch is bit-identical to ``block=1``)."""
    _check_engine(engine, placement, prefetch)
    if quant is not None:
        pcfg = dataclasses.replace(pcfg, comm=CommConfig(quant=quant))
    policy = resolve_policy(selection)
    fused_selection = engine == "batched" and not _force_host_selection
    block = check_block(block, engine,
                        force_host_selection=_force_host_selection,
                        eval_every=pcfg.eval_every)
    tm = resolve_threat_model(malicious, attack, threat_model)
    rng = np.random.default_rng(pcfg.seed)
    key = jax.random.PRNGKey(pcfg.seed)
    key, k0 = jax.random.split(key)
    theta = module.init(k0)
    x0, y0 = jnp.asarray(data.x0), jnp.asarray(data.y0)
    hist = History()
    d_o = data.x0.shape[0]
    d_cl = _count_params(theta[0])
    d_c = cut_width(module, theta[0], data.x0)
    tel = resolve_telemetry(
        telemetry if telemetry is not None else pcfg.telemetry,
        verbose=verbose, run="sfl", engine=engine, placement=placement,
        prefetch=prefetch, block=block, T=pcfg.T, M=pcfg.M, R=pcfg.R,
        selection=policy.name, fused_selection=fused_selection)
    if engine == "batched":
        from .engine import resident_data
        resident_data(data, tel)

    if block > 1:
        # Round-block execution: K FedAvg + selection-cascade rounds as one
        # scanned program, one stacked fetch per block; per-round History /
        # CommMeter replayed host-side (the SplitFed accounting is analytic,
        # so the replay is trivially bit-identical).
        from ..data.pipeline import RoundFeeder, plan_blocks
        from .engine import assemble_splitfed_block, splitfed_block_accept

        segments = plan_blocks(0, pcfg.T, block,
                               lambda t: (t % pcfg.eval_every == 0
                                          or t == pcfg.T - 1))

        _state = {"key": key}

        def _make_block(b):
            t0, k = segments[b]
            _state["key"], clusters_k, payload = assemble_splitfed_block(
                rng, _state["key"], data, pcfg, tm, t0, k, telemetry=tel)
            return clusters_k, payload

        feeder = RoundFeeder(_make_block, 0, len(segments), depth=prefetch,
                             telemetry=tel)
        try:
            for b, (t0, k) in enumerate(segments):
                tel.profile_tick(t0)
                if prefetch > 0:
                    with tel.span("round.feeder_wait", round=t0,
                                  depth=feeder.qsize()):
                        clusters_k, payload = feeder.get(b)
                else:
                    with tel.span("block.assemble", round=t0, k=k):
                        clusters_k, payload = feeder.get(b)
                theta, records = splitfed_block_accept(
                    module, theta, clusters_k, pcfg, t0, payload, x0, y0,
                    policy, placement=placement, telemetry=tel)
                for i, brec in enumerate(records):
                    t = t0 + i
                    clusters = clusters_k[i]
                    meter = CommMeter()
                    account_splitfed_round(meter, pcfg, clusters, d_o, d_c,
                                           d_cl)
                    selected = brec["selected"]
                    sel_cluster = clusters[selected]
                    rec = dict(round=t, selected=selected,
                               val_losses=brec["val_losses"],
                               selected_honest=cluster_is_honest(
                                   sel_cluster, tm.malicious),
                               comm=dataclasses.asdict(meter))
                    if t % pcfg.eval_every == 0 or t == pcfg.T - 1:
                        with eval_span(tel, data, t):
                            rec["test_acc"] = evaluate(
                                module, theta[0], theta[1], data.x_test,
                                data.y_test, pcfg.eval_batch)
                    hist.rounds.append(rec)
                    tel.record_round(t, rec,
                                     feeder_depth=(feeder.qsize()
                                                   if prefetch > 0 else None))
        finally:
            feeder.close()
            tel.close()
        return hist

    feeder = None
    if engine == "batched" and prefetch > 0:
        from ..data.pipeline import RoundFeeder
        from .engine import assemble_splitfed_round

        _state = {"key": key}

        def _make_round(t):
            clusters = make_clusters(rng, pcfg.M, pcfg.R)
            _state["key"], payload = assemble_splitfed_round(
                rng, _state["key"], data, clusters, pcfg, tm, t,
                telemetry=tel)
            return clusters, payload

        feeder = RoundFeeder(_make_round, 0, pcfg.T, depth=prefetch,
                             telemetry=tel)

    try:
        for t in range(pcfg.T):
            tel.profile_tick(t)
            meter = CommMeter()
            if feeder is not None:
                with tel.span("round.feeder_wait", round=t,
                              depth=feeder.qsize()):
                    clusters, prefetched = feeder.get(t)
            else:
                clusters = make_clusters(rng, pcfg.M, pcfg.R)
                prefetched = None
            if fused_selection:
                # Default batched path: FedAvg round + the policy selection
                # cascade in one compiled program, one stacked host fetch.
                from .engine import splitfed_round_accept
                key, theta, sel_rec = splitfed_round_accept(
                    module, theta, clusters, data, pcfg, tm, t, rng, key,
                    x0, y0, policy, placement=placement,
                    prefetched=prefetched, telemetry=tel)
                selected = sel_rec["selected"]
                val_losses = sel_rec["val_losses"]
                sel_cluster = clusters[selected]
            else:
                if engine == "batched":
                    from .engine import splitfed_round_batched
                    key, results = splitfed_round_batched(
                        module, theta, clusters, data, pcfg, tm, t, rng, key,
                        x0, y0, placement=placement, prefetched=prefetched,
                        with_stats=policy.needs_message_stats, telemetry=tel)
                else:
                    results = []
                    with tel.span("round.step", round=t):
                        for cluster in clusters:
                            gs, ps, sts = [], [], []
                            for client in cluster:
                                xs, ys = _sample_batches(rng, data.x[client],
                                                         data.y[client],
                                                         pcfg.E, pcfg.B)
                                key, sub = jax.random.split(key)
                                a = tm.attack_for(client, t)
                                if policy.needs_message_stats:
                                    g, p, _, st = client_update_stats(
                                        module, a, theta[0], theta[1],
                                        (xs, ys), pcfg.lr, sub,
                                        quant=pcfg.comm.quant)
                                    sts.append(np.asarray(st))
                                else:
                                    g, p, _ = client_update(
                                        module, a, theta[0], theta[1],
                                        (xs, ys), pcfg.lr, sub,
                                        quant=pcfg.comm.quant)
                                gs.append(g)
                                ps.append(p)
                            g_avg = jax.tree.map(
                                lambda *xs: sum(xs) / len(xs), *gs)
                            p_avg = jax.tree.map(
                                lambda *xs: sum(xs) / len(xs), *ps)
                            vloss, vacts = validation_loss(module, g_avg,
                                                           p_avg, x0, y0)
                            res = dict(gamma=g_avg, phi=p_avg, vacts=vacts,
                                       vloss=float(vloss), cluster=cluster)
                            if sts:
                                res["msg_stats"] = np.stack(sts)
                            results.append(res)
                from ..selection import host_score_context, score_and_rank
                with tel.span("round.select", round=t):
                    ctx = host_score_context(policy, module, results, x0, y0)
                    scores, elig, order = score_and_rank(policy, ctx)
                    selected = int(next(c for c in order if elig[c]))
                    theta = res_params(results[selected])
                val_losses = [res["vloss"] for res in results]
                sel_cluster = results[selected]["cluster"]
            account_splitfed_round(meter, pcfg, clusters, d_o, d_c, d_cl)
            rec = dict(round=t, selected=selected,
                       val_losses=val_losses,
                       selected_honest=cluster_is_honest(sel_cluster,
                                                         tm.malicious),
                       comm=dataclasses.asdict(meter))
            if t % pcfg.eval_every == 0 or t == pcfg.T - 1:
                with eval_span(tel, data, t):
                    rec["test_acc"] = evaluate(module, theta[0], theta[1],
                                               data.x_test, data.y_test,
                                               pcfg.eval_batch)
            hist.rounds.append(rec)
            tel.record_round(t, rec,
                             feeder_depth=(feeder.qsize()
                                           if feeder is not None else None))
    finally:
        if feeder is not None:
            feeder.close()
        tel.close()
    return hist
