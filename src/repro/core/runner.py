"""Device-placement-aware Pigeon round runner.

Pigeon-SL's global round is embarrassingly parallel across the R = N + 1
clusters: every cluster trains from the same theta^t, validates on the shared
set D_o, and only the argmin-loss winner survives.  Before this module the
repo carried the round in two divergent places — the protocol-level batched
engine (``core/engine.py``, vmap over clusters on one device) and the
launch-level pod-sharded step (``launch/steps.py``, shard_map over the "pod"
mesh axis) — which duplicated the train + validate + argmin + broadcast
program and could not share fixes.

:class:`RoundRunner` is the single source of truth.  A :class:`RoundSpec`
supplies the pure per-cluster programs (``train_cluster``, an optional
``combine`` fan-in — SplitFed's FedAvg — and ``validate``); the runner
compiles the cluster-parallel round under a pluggable *placement policy*:

  * ``placement="vmap"``    — ``jax.vmap`` over the cluster axis, one device
                              (the protocol engine's historical strategy);
  * ``placement="sharded"`` — the cluster axis laid over a mesh axis
                              (default ``"pod"``) via ``shard_map``; each
                              shard runs a vmap over its local cluster slice,
                              so R need not equal the device count (any mesh
                              whose cluster-axis size divides R works).

A third entry level, :meth:`RoundRunner.sweep`, runs S independent protocol
replicas (the multi-seed sweep) with per-seed argmin selection on device —
under vmap a second seed-level ``jax.vmap``, under the sharded placement a
2-D ``(seed, cluster)`` mesh (default axes ``("seed", "pod")``) so the
S x R replica grid lays out over real devices.

Both placements run the *same* ``cluster_map`` body, so they are numerically
equivalent by construction — the CPU equivalence suite
(``tests/test_runner.py``) checks selection, losses and CommMeter history
against the sequential oracle under a forced 8-virtual-device host mesh.

Selection is pluggable: a :class:`~repro.selection.SelectionPolicy` bound via
the runner's ``select=`` hook supplies the score/eligibility stages wherever
a winner is chosen inside the compiled program — :meth:`RoundRunner.round_fn`
(launch layer), :meth:`RoundRunner.sweep` (per-seed selection), and
:meth:`RoundRunner.accept`, the fused score -> rank -> verify -> commit
cascade (``repro.selection.cascade``) that replaced the protocol drivers'
host-side selection loop on the default batched path: candidate ranks as
data, handoff distances via the ``kernels/tamper_check`` Pallas kernel,
rejection as a ``jnp.where`` mask, one stacked host fetch per round.

Consumers:

  * ``core/engine.py`` binds :func:`protocol_round_spec` (client-chain scan +
    ``AttackVec`` threat-model lanes + shared-set validation) and uses
    :meth:`RoundRunner.accept` on the default path; the host-side reference
    cascade (:meth:`RoundRunner.candidates` + ``repro.selection.select_host``)
    remains for the sequential oracle and param-tamper threat models, whose
    handoff tampering consumes the protocol key per visited candidate.
  * ``launch/steps.py`` binds a ``Model``-level spec and uses
    :meth:`RoundRunner.round_fn` — the full round (policy selection + winner
    broadcast inside the compiled program), lowered under GSPMD/manual pod
    sharding by the dry-run driver.
"""
from __future__ import annotations

import dataclasses
import time
import weakref
from functools import lru_cache
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

Pytree = Any

PLACEMENTS = ("vmap", "sharded")

# Live-runner registry for telemetry introspection
# (``repro.telemetry.metrics.jit_cache_stats``): weak references only, so
# registration never extends a runner's lifetime past its cache entry.
_LIVE_RUNNERS: "weakref.WeakSet" = weakref.WeakSet()


def live_runners() -> list:
    """The RoundRunner instances currently alive (telemetry introspection)."""
    return list(_LIVE_RUNNERS)


def check_placement(placement: str) -> None:
    if placement not in PLACEMENTS:
        raise ValueError(f"placement={placement!r} must be one of {PLACEMENTS}")


# ---------------------------------------------------------------------------
# shared primitives
# ---------------------------------------------------------------------------

def onehot_select(stacked: Pytree, sel: jnp.ndarray) -> Pytree:
    """Pick index ``sel`` along each leaf's leading axis via a one-hot
    contraction: lowers to one masked reduction per leaf instead of the
    gather+full-replicate path GSPMD emits for dynamic indexing.  The mask is
    applied with ``jnp.where`` rather than multiplication so Inf/NaN in
    *unselected* slots (e.g. a diverged malicious cluster) cannot poison the
    selected values through ``0 * inf = nan``."""

    def pick(x):
        mask = (jnp.arange(x.shape[0]) == sel).reshape((-1,) + (1,) * (x.ndim - 1))
        masked = jnp.where(mask, x.astype(jnp.float32), jnp.float32(0.0))
        return jnp.sum(masked, axis=0).astype(x.dtype)

    return jax.tree.map(pick, stacked)


def broadcast_winner(winner: Pytree, stacked: Pytree) -> Pytree:
    """The paper's winner hand-off: every cluster slot of the next round
    starts from the selected cluster's parameters."""
    return jax.tree.map(
        lambda w, full: jnp.broadcast_to(w[None], full.shape).astype(full.dtype),
        winner, stacked)


@lru_cache(maxsize=None)
def cluster_mesh(r: int, max_devices: Optional[int] = None) -> Mesh:
    """1-D ("pod",) mesh over the largest divisor of R that fits the
    available devices — every shard then carries an equal R_local slice of
    the cluster axis (R_local = 1 when R <= device count)."""
    devs = jax.devices()
    n = min(len(devs), max_devices if max_devices else len(devs))
    return Mesh(np.array(devs[:_largest_divisor(r, n)]), ("pod",))


def _largest_divisor(n: int, cap: int) -> int:
    """Largest divisor of ``n`` that is <= ``cap`` (>= 1 always: a cap of
    zero or below degrades to the trivial divisor instead of dividing by
    zero — prime R on a 1-device host must still yield a valid mesh)."""
    d = max(1, min(n, cap))
    while n % d:
        d -= 1
    return d


@lru_cache(maxsize=None)
def sweep_mesh(s: int, r: int, max_devices: Optional[int] = None) -> Mesh:
    """2-D ("seed", "pod") mesh for the multi-seed sweep: the factorisation
    of the available devices into (divisor of S) x (divisor of R) that covers
    the most devices, so the S x R replica grid spreads as wide as the
    hardware allows (ties resolved toward the wider cluster axis — the
    cluster dimension is the paper's dominant parallelism).  The ``sn=1``
    seed never loses to worse factorisations: when neither S nor R factor
    against the device count (both prime, say), the result degrades to the
    widest 1-D cluster mesh (``1 x _largest_divisor(r, n)``), never below
    it."""
    devs = jax.devices()
    n = max(1, min(len(devs), max_devices if max_devices else len(devs)))
    best_s, best_r = 1, _largest_divisor(r, n)      # widest 1-D fallback
    for sn in range(1, min(s, n) + 1):
        if s % sn:
            continue
        rn = _largest_divisor(r, n // sn)
        if sn * rn > best_s * best_r or (sn * rn == best_s * best_r
                                         and rn > best_r):
            best_s, best_r = sn, rn
    return Mesh(np.array(devs[: best_s * best_r]).reshape(best_s, best_r),
                ("seed", "pod"))


def _normalize_manual_axes(manual_axes) -> frozenset:
    return frozenset((manual_axes,) if isinstance(manual_axes, str)
                     else manual_axes)


def _auto_axes(mesh: Mesh, manual_axes) -> list:
    manual = _normalize_manual_axes(manual_axes)
    return [a for a in mesh.axis_names if a not in manual]


def _apply_shard_map(fn, mesh: Mesh, in_specs, out_specs, manual_axes):
    """``jax.shard_map`` over ``manual_axes`` (the manually-mapped axes);
    any other mesh axes stay GSPMD-auto."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False,
                         axis_names=set(_normalize_manual_axes(manual_axes)))


def backend_supports_partial_auto(mesh: Mesh, manual_axes) -> bool:
    """Partial-auto shard_map (manual cluster axis + GSPMD-auto data/model
    axes) lowers fine everywhere but cannot *execute* on the XLA CPU backend
    when the auto axes span more than one device — CPU has no PartitionId
    under SPMD, so XLA crashes with an inscrutable error at run time."""
    auto = _auto_axes(mesh, manual_axes)
    auto_size = int(np.prod([mesh.shape[a] for a in auto], dtype=np.int64))
    if auto_size <= 1:
        return True
    return not all(d.platform == "cpu" for d in mesh.devices.flat)


def check_partial_auto_backend(mesh: Mesh, manual_axes) -> None:
    """Raise a clear error instead of letting XLA crash (ROADMAP open item:
    CPU pods + partial-auto shard_map).  Called on the *execution* entry
    points only — dry-run lowering/compilation of the same program is
    supported on every backend and must stay gate-free."""
    if backend_supports_partial_auto(mesh, manual_axes):
        return
    auto = _auto_axes(mesh, manual_axes)
    raise RuntimeError(
        f"partial-auto shard_map cannot execute on the CPU backend: mesh "
        f"{dict(mesh.shape)} has GSPMD-auto axes {auto} spanning "
        f"{np.prod([mesh.shape[a] for a in auto])} devices, and XLA CPU has "
        f"no PartitionId under SPMD.  Use a fully-manual 1-D cluster mesh on "
        f"CPU (mesh=None lets the runner build one), or run on TPU/GPU; "
        f"dry-run lowering of this program on CPU remains supported.")


# ---------------------------------------------------------------------------
# the round program
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RoundSpec:
    """The pure per-cluster programs of one Pigeon round.

    ``train_cluster(params, inputs) -> (params', train_aux)`` — one cluster's
    whole training phase (for the protocol engine: the within-cluster client
    chain; for SplitFed: all clients in parallel, leaving a leading client
    axis on ``params'``; for the launch layer: one SPMD train step).

    ``combine(params') -> cluster_params`` — optional fan-in applied between
    training and validation, for round families whose cluster model is an
    *aggregate* of per-client results rather than the chain's final state:
    SplitFed binds FedAvg (mean over the client axis ``train_cluster`` left
    on its output).  ``None`` (the default) means ``train_cluster`` already
    returns the cluster model.

    ``validate(cluster_params, val) -> (vloss, val_aux)`` — the shared-set
    validation forward (Section III-C).  ``val_aux`` carries whatever the
    consumer needs alongside the loss (the protocol engine keeps the cut
    activations for the tamper check; the launch spec returns None).

    The optional selection hooks feed the pluggable policies
    (``repro.selection``); a policy whose feature needs the bound spec cannot
    satisfy is rejected at program-build time:

    ``validate_sharded(cluster_params, val, k) -> (vloss, (k',) shard
    losses, val_aux)`` — shared-set validation split into (up to) ``k``
    equal D_o shards, for the median-of-means family of scores.

    ``handoff_acts(cluster_params, val) -> acts`` — the re-transmission a
    next-round first client would produce from the handed-off parameters;
    the fused verify stage compares it against ``val_aux`` with the
    ``kernels/tamper_check`` distance.

    ``train_summary(stacked_train_aux) -> (R,)`` — per-cluster train metric
    for the drivers' single History fetch (protocol: mean client loss).

    ``message_stats(stacked_train_aux) -> (R, M_bar, S)`` — per-client
    transmitted-message statistics for anomaly-scoring policies (requires a
    ``with_stats`` train program).
    """
    train_cluster: Callable[[Pytree, Any], Tuple[Pytree, Any]]
    validate: Callable[[Pytree, Any], Tuple[jnp.ndarray, Any]]
    combine: Optional[Callable[[Pytree], Pytree]] = None
    validate_sharded: Optional[Callable[[Pytree, Any, int],
                                        Tuple[jnp.ndarray, jnp.ndarray, Any]]] = None
    handoff_acts: Optional[Callable[[Pytree, Any], jnp.ndarray]] = None
    train_summary: Optional[Callable[[Any], jnp.ndarray]] = None
    message_stats: Optional[Callable[[Any], jnp.ndarray]] = None


@dataclasses.dataclass(frozen=True)
class VerifyConfig:
    """The fused cascade's verification stage: compare each candidate's
    handoff transmission against its validation-time activations
    (``kernels/tamper_check``) and reject candidates beyond ``tol``.

    ``recompute`` controls where the transmission comes from: True re-derives
    it from the handed-off parameters (``RoundSpec.handoff_acts`` — needed
    whenever something inside the program could perturb the handoff); False
    reuses the validation-time activations directly.  The protocol drivers'
    fused path runs with False: its precondition (no param-tamper families —
    those pin selection to the host cascade) makes the re-transmission equal
    to the validation activations *by construction*, so recomputing R client
    forwards per round would only confirm an identity.  The masked cascade,
    kernel distance and Table I re-transmission accounting stay live either
    way."""
    enabled: bool = True
    tol: float = 1e-4
    recompute: bool = True


def cluster_map(spec: RoundSpec, params: Pytree, inputs: Pytree, val: Pytree,
                params_stacked: bool = False):
    """Train + (combine +) validate every cluster on the leading axis of
    ``inputs`` — THE one copy of the Pigeon round math, shared by both
    placements (and by the multi-seed sweep, which vmaps it once more over
    seeds).

    Returns ``(params_R, train_aux_R, vlosses_R, val_aux_R)``.  When
    ``params_stacked`` the params already carry the leading cluster axis
    (each cluster trains its own replica, the launch-layer layout); otherwise
    a single params pytree is broadcast into every cluster (the protocol
    layout, where all clusters start from theta^t)."""

    def one(params_r, inputs_r):
        new_p, aux = spec.train_cluster(params_r, inputs_r)
        if spec.combine is not None:
            new_p = spec.combine(new_p)
        vloss, vaux = spec.validate(new_p, val)
        return new_p, aux, vloss, vaux

    return jax.vmap(one, in_axes=(0 if params_stacked else None, 0))(params, inputs)


def select_map(spec: RoundSpec, policy, params: Pytree, inputs: Pytree,
               val: Pytree, params_stacked: bool = False):
    """:func:`cluster_map` + the selection features ``policy`` declares it
    needs: ``(params_R, train_aux_R, vlosses_R, val_aux_R, shard_losses)``
    where ``shard_losses`` is ``(R, K)`` (via the spec's ``validate_sharded``
    hook) or None.  The default argmin policy takes the plain
    :func:`cluster_map` path, so its round program is unchanged."""
    if policy.shard_count <= 0:
        new_p, aux, vloss, vaux = cluster_map(spec, params, inputs, val,
                                              params_stacked)
        return new_p, aux, vloss, vaux, None
    if spec.validate_sharded is None:
        raise ValueError(f"selection policy {policy.name!r} needs sharded "
                         f"validation, which this RoundSpec does not provide")

    def one(params_r, inputs_r):
        new_p, aux = spec.train_cluster(params_r, inputs_r)
        if spec.combine is not None:
            new_p = spec.combine(new_p)
        vloss, shard_l, vaux = spec.validate_sharded(new_p, val,
                                                     policy.shard_count)
        return new_p, aux, vloss, vaux, shard_l

    return jax.vmap(one, in_axes=(0 if params_stacked else None, 0))(params, inputs)


def policy_context(spec: RoundSpec, policy, aux, vlosses, shard_losses):
    """Assemble the in-program :class:`~repro.selection.ScoreContext` —
    features must already be gathered across the full cluster axis (the
    sharded placement all-gathers them first), so policy stages stay pure
    jnp with no collectives."""
    from ..selection import ScoreContext
    stats = None
    if policy.needs_message_stats:
        if spec.message_stats is None:
            raise ValueError(f"selection policy {policy.name!r} needs "
                             f"transmitted-message statistics, which this "
                             f"RoundSpec does not surface")
        stats = spec.message_stats(aux)
    return ScoreContext(vlosses=vlosses, shard_losses=shard_losses,
                        message_stats=stats)


def policy_scores(policy, ctx):
    """(scores, eligibility) with the all-ineligible fallback applied."""
    scores = policy.score(ctx).astype(jnp.float32)
    elig = policy.eligible(ctx, scores)
    elig = jnp.where(jnp.any(elig), elig, jnp.ones_like(elig))
    return scores, elig


def masked_argmin(scores: jnp.ndarray, elig: jnp.ndarray) -> jnp.ndarray:
    """The one copy of the in-program winner rule (ineligible candidates
    sentinel to +inf) — vmap, sharded and sweep placements all call this, so
    their documented bit-for-bit agreement cannot drift."""
    return jnp.argmin(jnp.where(elig, scores,
                                jnp.float32(jnp.inf))).astype(jnp.int32)


def policy_choose(spec: RoundSpec, policy, aux, vlosses, shard_losses):
    """In-program winner index under a policy: masked argmin over scores."""
    ctx = policy_context(spec, policy, aux, vlosses, shard_losses)
    scores, elig = policy_scores(policy, ctx)
    return masked_argmin(scores, elig)


def _spec_train_summary(spec: RoundSpec, aux, vlosses):
    if spec.train_summary is None:
        return jnp.zeros_like(vlosses, dtype=jnp.float32)
    return spec.train_summary(aux).astype(jnp.float32)


def sweep_map(spec: RoundSpec, params: Pytree, inputs: Pytree, val: Pytree,
              params_stacked: bool = False, policy=None):
    """S independent protocol replicas of one global round: per seed, run
    :func:`select_map`, select the policy-winning cluster (default: argmin
    validation loss) and carry the winner forward.  ``params`` leaves lead
    with the seed axis (plus a cluster axis when ``params_stacked``);
    ``inputs`` leaves with ``(seed, cluster)``.  Returns
    ``(winner_params_S, train_aux_SR, vlosses_SR, sel_S)`` — the same
    arithmetic (masked-f32 one-hot contraction) the sharded placement
    reduces with ``psum``, so the two placements agree bit-for-bit."""
    from ..selection import ARGMIN
    policy = ARGMIN if policy is None else policy
    new_p, aux, vlosses, _, shard_l = jax.vmap(
        lambda p, i: select_map(spec, policy, p, i, val, params_stacked)
    )(params, inputs)
    sels = jax.vmap(
        lambda a, vl, sl: policy_choose(spec, policy, a, vl, sl),
        in_axes=(0, 0, None if shard_l is None else 0))(aux, vlosses, shard_l)
    winners = jax.vmap(onehot_select)(new_p, sels)
    return winners, aux, vlosses, sels


class RoundRunner:
    """Compiles a :class:`RoundSpec` under a placement policy.

    Two entry levels:

    * :meth:`candidates_fn` / :meth:`candidates` — all R candidate outcomes,
      selection left to the caller (the host-side reference cascade in
      ``repro.selection.selector`` — the sequential oracle and the
      param-tamper fallback).
    * :meth:`accept_fn` / :meth:`accept` — the fused score -> rank -> verify
      -> commit cascade inside the compiled program: policy scores, masked
      rank walk, per-candidate handoff verification via the
      ``kernels/tamper_check`` distance, winner commit (or rollback when
      every candidate fails), one stacked host fetch
      (``(vlosses, train_summary, selected, detections, accepted)``).
      The protocol drivers' default batched path.
    * :meth:`round_fn` / :meth:`round` — the full round with policy selection
      and winner broadcast inside the compiled program (the launch-layer
      ``pigeon_round_step`` contract: returns ``(rebro, vlosses, sel)``).
    * :meth:`sweep_fn` / :meth:`sweep` — S whole protocol replicas with
      per-seed policy selection on device; the sharded placement lays the
      S x R replica grid over a 2-D ``(seed_axis, cluster_axis)`` mesh.

    ``select`` binds a :class:`~repro.selection.SelectionPolicy` (default:
    the paper's argmin); ``verify`` configures :meth:`accept`'s tamper-check
    stage.

    ``mesh`` is only consulted by the sharded placement; when omitted a 1-D
    host mesh sized to the largest divisor of R (:func:`cluster_mesh`) — or,
    for :meth:`sweep`, the widest 2-D ``(seed, pod)`` factorisation
    (:func:`sweep_mesh`) — is built per call shape.  ``cluster_axis`` /
    ``seed_axis`` name the mesh axes carrying cluster / replica parallelism;
    other axes stay GSPMD-auto, so the launch layer's ("pod", "data",
    "model") meshes keep their data/model sharding.  The jitted execution
    entries gate the partial-auto CPU combination
    (:func:`check_partial_auto_backend`) with a clear error instead of the
    XLA crash; the ``*_fn`` bodies stay gate-free for dry-run lowering."""

    def __init__(self, spec: RoundSpec, *, placement: str = "vmap",
                 mesh: Optional[Mesh] = None, cluster_axis: str = "pod",
                 seed_axis: str = "seed", params_stacked: bool = False,
                 select=None, verify: Optional[VerifyConfig] = None):
        from ..selection import ARGMIN
        check_placement(placement)
        self.spec = spec
        self.placement = placement
        self.mesh = mesh
        self.cluster_axis = cluster_axis
        self.seed_axis = seed_axis
        self.params_stacked = params_stacked
        self.select = ARGMIN if select is None else select
        self.verify = VerifyConfig() if verify is None else verify
        self._jitted: dict = {}
        # first-call wall time per jitted entry (trace + XLA compile +
        # first dispatch), read by telemetry's jit_cache_stats
        self._trace_compile_s: dict = {}
        _LIVE_RUNNERS.add(self)

    # -- pure, traceable bodies (jit / lower externally) --------------------

    def candidates_fn(self) -> Callable:
        """(params, inputs, val) -> (params_R, train_aux_R, vlosses_R,
        val_aux_R), all with leading cluster axis R."""
        if self.placement == "vmap":
            return lambda params, inputs, val: cluster_map(
                self.spec, params, inputs, val, self.params_stacked)
        return lambda params, inputs, val: self._sharded(
            params, inputs, val, select=False)

    def round_fn(self) -> Callable:
        """(params, inputs, val) -> (rebro_params_R, vlosses_R, sel): the
        full round with in-program policy selection + winner broadcast."""
        if self.placement == "vmap":
            def round_body(params, inputs, val):
                new_p, aux, vlosses, _, shard_l = select_map(
                    self.spec, self.select, params, inputs, val,
                    self.params_stacked)
                sel = policy_choose(self.spec, self.select, aux, vlosses,
                                    shard_l)
                rebro = broadcast_winner(onehot_select(new_p, sel), new_p)
                return rebro, vlosses, sel
            return round_body
        return lambda params, inputs, val: self._sharded(
            params, inputs, val, select=True)

    def accept_fn(self) -> Callable:
        """(params, inputs, val) -> (committed_params, fetch): the fused
        round-acceptance cascade.  ``committed_params`` is the accepted
        winner (theta^{t+1}) or the unchanged ``params`` when every
        candidate fails verification; ``fetch`` is the
        ``repro.selection.cascade.pack_fetch`` vector — the drivers' single
        host sync per round.  Protocol layout only (``params`` is the
        single theta broadcast into every cluster)."""
        if self.params_stacked:
            raise ValueError("accept_fn requires the protocol layout "
                             "(params_stacked=False): the commit stage "
                             "resolves the R candidates back to one theta")
        if self.verify.enabled and self.verify.recompute \
                and self.spec.handoff_acts is None:
            raise ValueError("verify.enabled with recompute needs the "
                             "RoundSpec handoff_acts hook")
        if self.placement == "vmap":
            return self._accept_vmap
        return lambda params, inputs, val: self._sharded_accept(
            params, inputs, val)

    def _verify_passed(self, new_p, vaux, val):
        """Per-candidate handoff verification: compare the first clients'
        re-transmission (re-derived from the handed-off parameters when
        ``verify.recompute``, else the validation-time transmission itself —
        see :class:`VerifyConfig`) against the validation-time activations
        with the Pallas tamper-check distance.  Returns a bool pass mask
        over the leading candidate axis."""
        from ..kernels.ops import tamper_distance
        if self.verify.recompute:
            recv = jax.vmap(lambda p: self.spec.handoff_acts(p, val))(new_p)
        else:
            recv = vaux
        dists = jax.vmap(tamper_distance)(vaux, recv)
        return dists <= jnp.float32(self.verify.tol), dists

    def _accept_vmap(self, params, inputs, val):
        from ..selection import masked_first_accept, pack_fetch
        spec, policy = self.spec, self.select
        new_p, aux, vlosses, vaux, shard_l = select_map(
            spec, policy, params, inputs, val, False)
        ctx = policy_context(spec, policy, aux, vlosses, shard_l)
        scores, elig = policy_scores(policy, ctx)
        if self.verify.enabled:
            passed, _ = self._verify_passed(new_p, vaux, val)
        else:
            passed = jnp.ones_like(elig)
        sel, det, acc = masked_first_accept(scores, elig, passed)
        winner = onehot_select(new_p, sel)
        committed = jax.tree.map(lambda w, old: jnp.where(acc, w, old),
                                 winner, params)
        fetch = pack_fetch(vlosses, _spec_train_summary(spec, aux, vlosses),
                           sel, det, acc)
        return committed, fetch

    def sweep_fn(self) -> Callable:
        """(params_S, inputs_SR, val) -> (winner_params_S, train_aux_SR,
        vlosses_SR, sel_S): one global round of S independent replicas with
        the per-seed policy selection inside the compiled program."""
        if self.placement == "vmap":
            return lambda params, inputs, val: sweep_map(
                self.spec, params, inputs, val, self.params_stacked,
                self.select)
        return self._sharded_sweep

    # -- round-block entries: K rounds as one lax.scan, one host fetch -------

    def accept_block_fn(self) -> Callable:
        """(params, block_inputs, val) -> (committed_params, fetches): K
        consecutive fused acceptance rounds chained as a single
        ``jax.lax.scan`` over the round axis.  ``block_inputs`` leaves lead
        with K (each step slice is exactly one :meth:`accept_fn` payload);
        the carry is theta and is donated at the jit boundary, so the scan
        reuses the parameter buffers in place.  ``fetches`` stacks the K
        per-round ``pack_fetch`` vectors to (K, 2R+3) — ONE host sync per
        block, from which the drivers replay per-round History/telemetry/
        CommMeter records bit-identically to per-round execution (the scan
        body IS the per-round accept program)."""
        body = self.accept_fn()

        def block_body(params, block_inputs, val):
            def step(theta, inputs):
                return body(theta, inputs, val)

            return jax.lax.scan(step, params, block_inputs)

        return block_body

    def sweep_block_fn(self) -> Callable:
        """(params_S, block_inputs, val) -> (winner_params_S, (vlosses_KSR,
        tlosses_KSR, sels_KS)): K sweep rounds as one scan.  The per-round
        train-loss reduction (mean over the client axis) moves inside the
        program so the stacked ys stay small — the same ``jnp.mean`` the
        per-round driver applies to the fetched aux, hence bit-identical."""
        body = self.sweep_fn()

        def block_body(params, block_inputs, val):
            def step(theta_s, inputs):
                new_thetas, aux, vlosses, sels = body(theta_s, inputs, val)
                tl = aux[0] if isinstance(aux, tuple) else aux
                return new_thetas, (vlosses, jnp.mean(tl, axis=-1), sels)

            return jax.lax.scan(step, params, block_inputs)

        return block_body

    def round_block_fn(self) -> Callable:
        """(stacked_params, block_batches, val) -> (rebro_params_R,
        (vlosses_KR, sels_K)): K full launch-layer rounds (in-program policy
        selection + winner broadcast) as one scan — the block variant of
        :meth:`round_fn` for the ``make_pigeon_round_step`` family.  The
        stacked-params carry is donated at the jit boundary."""
        body = self.round_fn()

        def block_body(params, block_batches, val):
            def step(stacked, batches):
                rebro, vlosses, sel = body(stacked, batches, val)
                return rebro, (vlosses, sel)

            return jax.lax.scan(step, params, block_batches)

        return block_body

    # -- job-pool entry: J jobs x K rounds, one program, one fetch -----------

    def pool_accept_block_fn(self) -> Callable:
        """(params_J, block_inputs_J, val_J, active_J) -> (committed_J,
        fetches_J): J independent jobs' round blocks batched onto a leading
        job lane of the :meth:`accept_block_fn` program.  Every leaf of
        ``params_J`` / ``block_inputs_J`` / ``val_J`` leads with J;
        ``active_J`` is a (J,) bool lane mask — a masked (idle) lane runs
        the same arithmetic on its placeholder payload but its commit is
        discarded (``jnp.where(active, new, old)``), so ragged pools cost no
        recompile.  ``fetches_J`` stacks to (J, K, 2R+3) — ONE host sync per
        pool block, from which the pool driver replays every lane's
        per-round records exactly as the solo driver would.  The per-lane
        body is literally the scan of :meth:`accept_fn`'s vmap cascade, so
        an active lane is bit-identical to running its job alone.

        Under ``placement="sharded"`` the JOB axis (not the cluster axis)
        lays over the mesh: jobs are embarrassingly parallel with no
        cross-lane collectives, so each shard just vmaps its local lane
        slice.  Protocol layout only, like :meth:`accept_fn`."""
        if self.params_stacked:
            raise ValueError("pool_accept_block_fn requires the protocol "
                             "layout (params_stacked=False)")
        if self.verify.enabled and self.verify.recompute \
                and self.spec.handoff_acts is None:
            raise ValueError("verify.enabled with recompute needs the "
                             "RoundSpec handoff_acts hook")
        body = self._accept_vmap

        def one_job(params, block_inputs, val, active):
            def step(theta, inputs):
                return body(theta, inputs, val)

            new_p, fetches = jax.lax.scan(step, params, block_inputs)
            committed = jax.tree.map(
                lambda n, o: jnp.where(active, n, o), new_p, params)
            return committed, fetches

        def pool_lanes(params, block_inputs, val, active):
            # bit-identity corner: a size-1 vmap vectorises the batch-mean
            # reductions differently from the unvmapped scan (last-float-bit
            # drift vs solo), so a single (local) lane runs ``one_job`` on
            # the squeezed tree — literally the solo block program — and the
            # lane axis is reshaped back on
            if active.shape[0] == 1:
                sq = lambda t: jax.tree.map(lambda a: a[0], t)
                c, f = one_job(sq(params), sq(block_inputs), sq(val),
                               active[0])
                return (jax.tree.map(lambda a: a[None], c),
                        jax.tree.map(lambda a: a[None], f))
            return jax.vmap(one_job)(params, block_inputs, val, active)

        if self.placement == "vmap":
            return pool_lanes

        def pool_sharded(params_j, block_inputs_j, val_j, active_j):
            ax = self.cluster_axis
            j = active_j.shape[0]
            mesh = self.mesh if self.mesh is not None else cluster_mesh(j)
            if j % mesh.shape[ax]:
                raise ValueError(f"J={j} not divisible by mesh axis "
                                 f"{ax!r}={mesh.shape[ax]}")
            fn = _apply_shard_map(
                pool_lanes,
                mesh, (P(ax), P(ax), P(ax), P(ax)), (P(ax), P(ax)), ax)
            return fn(params_j, block_inputs_j, val_j, active_j)

        return pool_sharded

    # -- sharded placement --------------------------------------------------

    def _gathered_context(self, aux, vloss, shard_l, ax):
        """All-gather the local selection features across the cluster mesh
        axis and build the global ScoreContext every shard scores
        identically (policy stages are pure jnp — no collectives inside)."""
        from ..selection import ScoreContext
        spec, policy = self.spec, self.select
        losses_g = jax.lax.all_gather(vloss, ax, tiled=True)          # (R,)
        shard_g = (None if shard_l is None
                   else jax.lax.all_gather(shard_l, ax, tiled=True))
        stats_g = None
        if policy.needs_message_stats:
            if spec.message_stats is None:
                raise ValueError(f"selection policy {policy.name!r} needs "
                                 f"transmitted-message statistics, which "
                                 f"this RoundSpec does not surface")
            stats_g = jax.lax.all_gather(spec.message_stats(aux), ax,
                                         tiled=True)
        return ScoreContext(vlosses=losses_g, shard_losses=shard_g,
                            message_stats=stats_g)

    def _psum_pick(self, new_p, sel, ax):
        """One-hot psum contraction of the global winner out of the local
        candidate slices (a single masked all-reduce per leaf)."""
        r_local = jax.tree.leaves(new_p)[0].shape[0]
        mine = (jax.lax.axis_index(ax) * r_local + jnp.arange(r_local)) == sel

        def pick(x):
            mask = mine.reshape((-1,) + (1,) * (x.ndim - 1))
            local = jnp.sum(jnp.where(mask, x.astype(jnp.float32),
                                      jnp.float32(0.0)),
                            axis=0)
            return jax.lax.psum(local, ax).astype(x.dtype)

        return jax.tree.map(pick, new_p)

    def _sharded(self, params, inputs, val, select: bool):
        ax = self.cluster_axis
        r = jax.tree.leaves(inputs)[0].shape[0]
        mesh = self.mesh if self.mesh is not None else cluster_mesh(r)
        if r % mesh.shape[ax]:
            raise ValueError(f"R={r} not divisible by mesh axis "
                             f"{ax!r}={mesh.shape[ax]}")

        def per_shard(params_s, inputs_s, val_s):
            # params_s: the local R_local slice (stacked) or the full
            # replicated pytree; inputs_s: the local cluster slice.
            if not select:
                return cluster_map(self.spec, params_s, inputs_s, val_s,
                                   self.params_stacked)
            new_p, aux, vloss, _, shard_l = select_map(
                self.spec, self.select, params_s, inputs_s, val_s,
                self.params_stacked)
            ctx = self._gathered_context(aux, vloss, shard_l, ax)
            scores, elig = policy_scores(self.select, ctx)
            sel = masked_argmin(scores, elig)
            rebro = broadcast_winner(self._psum_pick(new_p, sel, ax), new_p)
            return rebro, ctx.vlosses, sel

        p_spec = P(ax) if self.params_stacked else P()
        in_specs = (p_spec, P(ax), P())
        out_specs = ((P(ax), P(), P()) if select
                     else (P(ax), P(ax), P(ax), P(ax)))
        fn = _apply_shard_map(per_shard, mesh, in_specs, out_specs, ax)
        return fn(params, inputs, val)

    def _sharded_accept(self, params, inputs, val):
        from ..selection import masked_first_accept, pack_fetch
        ax = self.cluster_axis
        r = jax.tree.leaves(inputs)[0].shape[0]
        mesh = self.mesh if self.mesh is not None else cluster_mesh(r)
        if r % mesh.shape[ax]:
            raise ValueError(f"R={r} not divisible by mesh axis "
                             f"{ax!r}={mesh.shape[ax]}")
        spec, policy = self.spec, self.select

        def per_shard(params_s, inputs_s, val_s):
            new_p, aux, vloss, vaux, shard_l = select_map(
                spec, policy, params_s, inputs_s, val_s, False)
            ctx = self._gathered_context(aux, vloss, shard_l, ax)
            scores, elig = policy_scores(policy, ctx)
            if self.verify.enabled:
                passed_l, _ = self._verify_passed(new_p, vaux, val_s)
                passed = jax.lax.all_gather(passed_l, ax, tiled=True)
            else:
                passed = jnp.ones_like(elig)
            sel, det, acc = masked_first_accept(scores, elig, passed)
            winner = self._psum_pick(new_p, sel, ax)
            committed = jax.tree.map(lambda w, old: jnp.where(acc, w, old),
                                     winner, params_s)
            summary = jax.lax.all_gather(
                _spec_train_summary(spec, aux, vloss), ax, tiled=True)
            fetch = pack_fetch(ctx.vlosses, summary, sel, det, acc)
            return committed, fetch

        in_specs = (P(), P(ax), P())
        out_specs = (P(), P())
        fn = _apply_shard_map(per_shard, mesh, in_specs, out_specs, ax)
        return fn(params, inputs, val)

    def _sharded_sweep(self, params, inputs, val):
        ax, sax = self.cluster_axis, self.seed_axis
        leaf = jax.tree.leaves(inputs)[0]
        s, r = leaf.shape[0], leaf.shape[1]
        mesh = self.mesh if self.mesh is not None else sweep_mesh(s, r)
        if s % mesh.shape[sax] or r % mesh.shape[ax]:
            raise ValueError(f"(S={s}, R={r}) not divisible by mesh axes "
                             f"({sax!r}={mesh.shape[sax]}, "
                             f"{ax!r}={mesh.shape[ax]})")

        def per_shard(params_s, inputs_s, val_s):
            # params_s: (S_local, ...) [+ cluster dim when stacked];
            # inputs_s: the local (S_local, R_local, ...) replica block.
            new_p, aux, vloss, _, shard_l = jax.vmap(
                lambda p, i: select_map(self.spec, self.select, p, i, val_s,
                                        self.params_stacked)
            )(params_s, inputs_s)
            losses = jax.lax.all_gather(vloss, ax, axis=1, tiled=True)  # (S_local, R)
            shard_g = (None if shard_l is None
                       else jax.lax.all_gather(shard_l, ax, axis=1, tiled=True))
            stats_g = None
            if self.select.needs_message_stats:
                stats_g = jax.lax.all_gather(
                    jax.vmap(self.spec.message_stats)(aux), ax, axis=1,
                    tiled=True)

            def choose(vl, sl, st):
                from ..selection import ScoreContext
                ctx = ScoreContext(vlosses=vl, shard_losses=sl,
                                   message_stats=st)
                scores, elig = policy_scores(self.select, ctx)
                return masked_argmin(scores, elig)

            sels = jax.vmap(choose, in_axes=(
                0, None if shard_g is None else 0,
                None if stats_g is None else 0))(losses, shard_g, stats_g)
            r_local = vloss.shape[1]
            mine = (jax.lax.axis_index(ax) * r_local
                    + jnp.arange(r_local))[None, :] == sels[:, None]

            def pick(x):
                mask = mine.reshape(mine.shape + (1,) * (x.ndim - 2))
                local = jnp.sum(jnp.where(mask, x.astype(jnp.float32),
                                      jnp.float32(0.0)),
                                axis=1)
                return jax.lax.psum(local, ax).astype(x.dtype)

            return jax.tree.map(pick, new_p), aux, losses, sels

        p_spec = P(sax, ax) if self.params_stacked else P(sax)
        in_specs = (p_spec, P(sax, ax), P())
        out_specs = (P(sax), P(sax, ax), P(sax), P(sax))
        fn = _apply_shard_map(per_shard, mesh, in_specs, out_specs, (sax, ax))
        return fn(params, inputs, val)

    # -- jitted convenience entry points ------------------------------------

    def _check_executable(self, manual_axes) -> None:
        if self.placement == "sharded" and self.mesh is not None:
            check_partial_auto_backend(self.mesh, manual_axes)

    # Entries whose params/theta carry is donated at the jit boundary: the
    # drivers rebind theta every call (theta = accept(theta, ...)), so XLA
    # may reuse the carry buffers in place instead of allocating a second
    # parameter set per round.  "candidates" is NOT donated — the host-side
    # reference cascade (select_host) may roll back to the original theta —
    # and neither is "round", whose launch/test callers legitimately reuse
    # the same stacked params across runners.
    _DONATED = frozenset({"accept", "sweep", "accept_block", "sweep_block",
                          "round_block", "pool_accept_block"})

    ENTRIES = ("candidates", "round", "accept", "sweep", "accept_block",
               "sweep_block", "round_block", "pool_accept_block")

    def audit_body(self, which: str) -> Callable:
        """The un-jitted body of one entry — the static-analysis layer
        retraces this under alternative configs (e.g. ``enable_x64`` to
        surface weak-type f64 promotion) without touching the dispatch
        cache."""
        return {"candidates": self.candidates_fn, "round": self.round_fn,
                "accept": self.accept_fn, "sweep": self.sweep_fn,
                "accept_block": self.accept_block_fn,
                "sweep_block": self.sweep_block_fn,
                "round_block": self.round_block_fn,
                "pool_accept_block": self.pool_accept_block_fn}[which]()

    def donated_argnums(self, which: str) -> tuple:
        return (0,) if which in self._DONATED else ()

    def _compiled(self, which: str) -> Callable:
        fn = self._jitted.get(which)
        if fn is None:
            fn = jax.jit(self.audit_body(which),
                         donate_argnums=self.donated_argnums(which))
            self._jitted[which] = fn
        return fn

    def lower(self, which: str, *args):
        """Audit hook: the lowered (pre-compile) program of a jitted entry,
        donation flags included.  Shares ``_jitted`` with dispatch, so the
        auditor provably sees the same program object the drivers run."""
        return self._compiled(which).lower(*args)

    def _call(self, which: str, *args):
        """Invoke a jitted entry, recording the first call's wall time
        (trace + XLA compile + first dispatch) for telemetry.  Only the
        monotonic clock is read — no effect on the computation."""
        fn = self._compiled(which)
        if which in self._trace_compile_s:
            return fn(*args)
        t0 = time.perf_counter()
        out = fn(*args)
        self._trace_compile_s[which] = time.perf_counter() - t0
        return out

    def candidates(self, params, inputs, val):
        self._check_executable((self.cluster_axis,))
        return self._call("candidates", params, inputs, val)

    def round(self, params, inputs, val):
        self._check_executable((self.cluster_axis,))
        return self._call("round", params, inputs, val)

    def accept(self, params, inputs, val):
        """Fused round acceptance: (committed_params, fetch) — see
        :meth:`accept_fn`."""
        self._check_executable((self.cluster_axis,))
        return self._call("accept", params, inputs, val)

    def sweep(self, params, inputs, val):
        self._check_executable((self.seed_axis, self.cluster_axis))
        return self._call("sweep", params, inputs, val)

    def accept_block(self, params, block_inputs, val):
        """K scanned acceptance rounds, one stacked (K, 2R+3) fetch — see
        :meth:`accept_block_fn`.  The theta carry is donated."""
        self._check_executable((self.cluster_axis,))
        return self._call("accept_block", params, block_inputs, val)

    def sweep_block(self, params, block_inputs, val):
        self._check_executable((self.seed_axis, self.cluster_axis))
        return self._call("sweep_block", params, block_inputs, val)

    def pool_accept_block(self, params_j, block_inputs_j, val_j, active_j):
        """J jobs x K scanned acceptance rounds, one stacked (J, K, 2R+3)
        fetch — see :meth:`pool_accept_block_fn`.  The theta_J carry is
        donated."""
        self._check_executable((self.cluster_axis,))
        return self._call("pool_accept_block", params_j, block_inputs_j,
                          val_j, active_j)

    def round_block(self, params, block_batches, val):
        self._check_executable((self.cluster_axis,))
        return self._call("round_block", params, block_batches, val)


# ---------------------------------------------------------------------------
# the protocol-level binding (SplitModule + AttackVec lanes)
# ---------------------------------------------------------------------------

def sharded_validation_losses(module, phi, acts, y0, k: int) -> jnp.ndarray:
    """(k',) per-shard shared-set losses from the validation activations —
    THE one copy of the median-of-means shard arithmetic, shared by the
    pigeon and SplitFed spec bindings and the host selector
    (``repro.selection.selector._shard_loss_fn``)."""
    from ..selection import effective_shards
    kk = effective_shards(k, acts.shape[0])
    shard_acts = acts.reshape((kk, acts.shape[0] // kk) + acts.shape[1:])
    shard_y = y0.reshape((kk, y0.shape[0] // kk) + y0.shape[1:])
    return jax.vmap(lambda a, y: module.ap_loss(phi, a, y))(shard_acts,
                                                            shard_y)


def make_train_summary(with_stats: bool):
    """The SplitModule specs' ``train_summary`` hook: per-cluster mean
    client loss out of the (losses[, stats]) aux convention."""

    def train_summary(aux):
        losses = aux[0] if with_stats else aux
        return jnp.mean(losses, axis=-1)

    return train_summary

@lru_cache(maxsize=None)
def protocol_round_spec(module, lr: float, with_stats: bool = False,
                        quant: Optional[str] = None) -> RoundSpec:
    """Pigeon per-cluster programs over a ``SplitModule``: the within-cluster
    client-chain scan with the AttackVec threat-model lanes from the
    adversary subsystem (``inputs = (xs, ys, avec, keys)``, every leaf with
    leading axis M_bar), and shared-set validation returning the cut
    activations the tamper check compares against (``val = (x0, y0)``).

    The selection hooks bind the full policy feature set: sharded shared-set
    validation (median-of-means), the handoff re-transmission (the fused
    verify stage), and — under ``with_stats`` — the per-client
    transmitted-message statistics (``core.split.message_stats``) that the
    anomaly-scoring policies read.  ``with_stats=False`` compiles exactly
    the pre-selection-subsystem round program."""
    from .split import client_update_vec_impl, client_update_vec_stats_impl

    def train_cluster(theta, inputs):
        xs_c, ys_c, av_c, keys_c = inputs
        gamma, phi = theta

        def per_client(carry, inp):
            g, p = carry
            x, y, av, k = inp
            if with_stats:
                g, p, loss, stats = client_update_vec_stats_impl(
                    module, av, g, p, (x, y), lr, k, quant=quant)
                return (g, p), (loss, stats)
            g, p, loss = client_update_vec_impl(module, av, g, p, (x, y), lr,
                                                k, quant=quant)
            return (g, p), loss

        (g, p), aux = jax.lax.scan(per_client, (gamma, phi),
                                   (xs_c, ys_c, av_c, keys_c))
        return (g, p), aux

    def validate(theta, val):
        g, p = theta
        x0, y0 = val
        acts = module.client_forward(g, x0)
        return module.ap_loss(p, acts, y0), acts

    def validate_sharded(theta, val, k):
        g, p = theta
        x0, y0 = val
        acts = module.client_forward(g, x0)
        shard_losses = sharded_validation_losses(module, p, acts, y0, k)
        # History's vloss stays the exact full-set loss (same op as
        # ``validate``, the forward is shared); the shards only feed scores
        return module.ap_loss(p, acts, y0), shard_losses, acts

    def handoff_acts(theta, val):
        return module.client_forward(theta[0], val[0])

    return RoundSpec(
        train_cluster, validate,
        validate_sharded=validate_sharded,
        handoff_acts=handoff_acts,
        train_summary=make_train_summary(with_stats),
        message_stats=(lambda aux: aux[1]) if with_stats else None)


@lru_cache(maxsize=None)
def protocol_runner(module, lr: float, placement: str = "vmap",
                    with_stats: bool = False, select=None,
                    quant: Optional[str] = None) -> RoundRunner:
    """Cached per (module, lr, placement, stats, policy, quant) so every
    round reuses one compiled program — the protocol layout (theta broadcast
    into all clusters)."""
    return RoundRunner(protocol_round_spec(module, lr, with_stats, quant),
                       placement=placement, select=select)


@lru_cache(maxsize=None)
def protocol_accept_runner(module, lr: float, placement: str, select,
                           tamper_check: bool, tamper_tol: float,
                           quant: Optional[str] = None) -> RoundRunner:
    """The fused-acceptance runner the protocol drivers use on the default
    batched path: the policy's score/eligibility stages + the masked
    rank/verify/commit cascade compiled into one round program."""
    spec = protocol_round_spec(module, lr,
                               with_stats=select.needs_message_stats,
                               quant=quant)
    # recompute=False: this runner only ever runs under the no-param-tamper
    # precondition (engine.pigeon_round_accept asserts it), where the
    # re-transmission equals the validation activations by construction.
    return RoundRunner(spec, placement=placement, select=select,
                       verify=VerifyConfig(enabled=tamper_check,
                                           tol=tamper_tol,
                                           recompute=False))
