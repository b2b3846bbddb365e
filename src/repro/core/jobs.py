"""Job-pool execution layer: megabatching concurrent Pigeon-SL jobs.

The production regime the ROADMAP targets is many concurrent *small* jobs —
per-tenant protocol instances — not one big one, and a solo ``run_pigeon``
pays its own dispatch, compile and host-sync cost per round.  The sweep path
proves S x R protocol replicas share one device program and round-block
fusion proves K rounds share one dispatch; this module combines them at the
job level:

* :class:`JobSpec` — one tenant's run: module, data, protocol config, threat
  model, selection policy, quant format, checkpoint/resume knobs.
* :class:`JobPool` — shape-buckets compatible specs (same module / lr /
  M / R / E / B / tamper config / policy / quant / data shapes — everything
  that shapes or parameterises the compiled round program).  Seeds, horizons
  T, threat models and eval/checkpoint cadences stay free per job: threat
  state is data (``AttackVec`` lanes), not program.
* :func:`run_job_pool` — executes each bucket round-block by round-block on
  the :meth:`RoundRunner.pool_accept_block` entry: J jobs stacked onto a
  leading job lane of the ``accept_block`` scan, masked lanes for ragged
  pools, ONE compiled program per bucket and ONE stacked ``(J, K, 2R+3)``
  host fetch per block.  Lanes recycle elastically — a job that finishes its
  T rounds frees its lane, refilled from the bucket queue between blocks —
  and results fan out to per-job :class:`History`, crash-atomic per-job
  checkpoints and job-tagged telemetry round events.

Bit-identity contract: the pooled body is literally the scan of the solo
fused cascade, per-lane host assembly consumes each job's numpy RNG and JAX
key streams in exactly the solo order, and the CommMeter replay reuses the
solo accounting helpers — so every job's ``History`` is bit-identical to
running it alone (``tests/test_jobs.py`` pins this across placements, block
sizes and mid-pool refill).

Preconditions (validated up front, raising instead of degrading — a pool
cannot fall back to host-side selection for one lane): no param-tamper
threat models, no Pigeon-SL+ sub-rounds.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..adversary import ThreatModel, resolve_threat_model
from ..selection import resolve_policy, unpack_block_fetch
from ..telemetry import pool_gauges, resolve_telemetry
from .attacks import Attack, HONEST
from .clustering import cluster_is_honest
from .comm import CommConfig
from .protocol import (ClientData, CommMeter, History, ProtocolConfig,
                       _count_params, account_client_turn,
                       account_handoff_recheck, account_param_transfer,
                       account_validation, check_block, cut_width, eval_span,
                       evaluate)
from .runner import check_placement, protocol_accept_runner
from .split import SplitModule

Pytree = Any


@dataclasses.dataclass(frozen=True, eq=False)
class JobSpec:
    """One tenant's Pigeon-SL run, as the pool scheduler sees it.

    ``name`` keys the job's History / checkpoints / telemetry tags and must
    be unique within a pool.  ``threat_model`` / ``(malicious, attack)``
    follow the ``run_pigeon`` resolution rules; ``selection`` is a policy
    name or instance; ``quant`` overrides ``pcfg.comm`` exactly as the solo
    driver's kwarg does."""
    name: str
    module: SplitModule
    data: ClientData
    pcfg: ProtocolConfig
    malicious: Optional[Set[int]] = None
    attack: Attack = HONEST
    threat_model: Optional[ThreatModel] = None
    selection: Any = "argmin"
    quant: Optional[str] = None
    checkpoint_path: Optional[str] = None
    checkpoint_every: int = 1
    resume: bool = False


def _resolved_pcfg(spec: JobSpec) -> ProtocolConfig:
    if spec.quant is None:
        return spec.pcfg
    return dataclasses.replace(spec.pcfg, comm=CommConfig(quant=spec.quant))


def validate_job(spec: JobSpec, block: int = 1) -> Tuple[Any, ThreatModel,
                                                         ProtocolConfig]:
    """Resolve and validate one spec for pool execution: returns
    ``(policy, threat_model, resolved_pcfg)``.  Conditions the solo driver
    degrades per run (param-tamper pinning selection to the host cascade)
    RAISE here — a pooled lane cannot switch execution model without
    breaking the shared program — while the solo :func:`check_block`
    cadence warnings still apply per job."""
    policy = resolve_policy(spec.selection)
    tm = resolve_threat_model(spec.malicious, spec.attack, spec.threat_model)
    pcfg = _resolved_pcfg(spec)
    if tm.has_param_tamper:
        raise ValueError(
            f"job {spec.name!r}: param-tamper threat models need host-side "
            f"selection (per-candidate key splits) and cannot run in a job "
            f"pool — run it solo via run_pigeon")
    if pcfg.M % pcfg.R:
        raise ValueError(f"job {spec.name!r}: M={pcfg.M} not divisible by "
                         f"R={pcfg.R}")
    check_block(block, "batched", plus=False, has_param_tamper=False,
                force_host_selection=False, eval_every=pcfg.eval_every,
                checkpoint_path=spec.checkpoint_path,
                checkpoint_every=spec.checkpoint_every)
    return policy, tm, pcfg


def bucket_key(spec: JobSpec) -> tuple:
    """The shape-bucket key: everything that parameterises or shapes the
    compiled pool program.  Jobs agreeing on this key share ONE compiled
    program (the same lru-cached :func:`protocol_accept_runner` the solo
    driver uses); seed, T, threat model and sync cadences are data or host
    schedule, never program."""
    pcfg = _resolved_pcfg(spec)
    d = spec.data
    return (spec.module, pcfg.lr, pcfg.M, pcfg.R, pcfg.E, pcfg.B,
            pcfg.tamper_check, pcfg.tamper_tol, resolve_policy(spec.selection),
            pcfg.comm.quant,
            d.x.shape, d.x.dtype.str, d.y.shape, d.y.dtype.str,
            d.x0.shape, d.x0.dtype.str, d.y0.shape, d.y0.dtype.str)


class JobPool:
    """Validated, bucketed job queue.  ``buckets()`` yields the spec groups
    in first-seen order; specs inside a bucket keep submission order (the
    lane-refill order)."""

    def __init__(self, specs: Sequence[JobSpec], *, block: int = 1,
                 placement: str = "vmap"):
        check_placement(placement)
        names = [s.name for s in specs]
        if len(set(names)) != len(names):
            dupes = sorted({n for n in names if names.count(n) > 1})
            raise ValueError(f"duplicate job names in pool: {dupes}")
        if not specs:
            raise ValueError("empty job pool")
        self.specs = list(specs)
        self.block = block
        self.placement = placement
        self._resolved = [validate_job(s, block) for s in specs]
        self._buckets: Dict[tuple, List[int]] = {}
        for i, s in enumerate(specs):
            self._buckets.setdefault(bucket_key(s), []).append(i)

    def buckets(self) -> List[List[int]]:
        """Job indices per shape bucket, first-seen bucket order."""
        return list(self._buckets.values())

    def resolved(self, i: int) -> Tuple[Any, ThreatModel, ProtocolConfig]:
        return self._resolved[i]


# ---------------------------------------------------------------------------
# per-job protocol state (solo-init discipline)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _JobState:
    spec: JobSpec
    policy: Any
    tm: ThreatModel
    pcfg: ProtocolConfig
    rng: np.random.Generator
    key: jax.Array
    theta: Pytree
    t: int                          # next round to run
    hist: History
    d_cl: int
    d_c: int
    d_o: int
    x0: jnp.ndarray
    y0: jnp.ndarray
    terminal: bool = False          # resumed past T-1: nothing to train

    def ckpt_due(self, t: int) -> bool:
        return self.spec.checkpoint_path is not None and (
            (t + 1) % self.spec.checkpoint_every == 0
            or t == self.pcfg.T - 1)

    def is_sync(self, t: int) -> bool:
        return (t % self.pcfg.eval_every == 0 or t == self.pcfg.T - 1
                or self.ckpt_due(t))


def _init_job(spec: JobSpec, policy, tm: ThreatModel,
              pcfg: ProtocolConfig) -> _JobState:
    """Mirror of ``run_pigeon``'s init + resume preamble, per job: the same
    RNG/key/init draws in the same order, the same on-stream checkpoint
    restore, the same terminal-resume short-circuit."""
    rng = np.random.default_rng(pcfg.seed)
    key = jax.random.PRNGKey(pcfg.seed)
    key, k0 = jax.random.split(key)
    theta = spec.module.init(k0)
    start_round = 0
    if spec.resume and spec.checkpoint_path is not None:
        from ..checkpoint import (CorruptCheckpointError, load_checkpoint,
                                  restore_protocol_state, restore_pytree)
        from .clustering import make_clusters
        try:
            _, meta = load_checkpoint(spec.checkpoint_path)
            theta = restore_pytree(spec.checkpoint_path, theta)
            start_round = int(meta.get("round", -1)) + 1
            if "rng_state" in meta:
                key = restore_protocol_state(rng, key, meta)
            else:
                for _ in range(start_round):
                    make_clusters(rng, pcfg.M, pcfg.R)
        except FileNotFoundError:
            start_round = 0
        except CorruptCheckpointError as e:
            import warnings
            warnings.warn(f"job {spec.name!r}: ignoring corrupt checkpoint "
                          f"{spec.checkpoint_path!r} ({e}); starting from "
                          f"round 0", stacklevel=2)
            start_round = 0
    st = _JobState(
        spec=spec, policy=policy, tm=tm, pcfg=pcfg, rng=rng, key=key,
        theta=theta, t=start_round, hist=History(),
        d_cl=_count_params(theta[0]),
        d_c=cut_width(spec.module, theta[0], spec.data.x0),
        d_o=spec.data.x0.shape[0],
        x0=jnp.asarray(spec.data.x0), y0=jnp.asarray(spec.data.y0))
    if start_round >= pcfg.T:
        import warnings
        warnings.warn(
            f"job {spec.name!r}: checkpoint {spec.checkpoint_path!r} is at "
            f"round {start_round - 1} >= T-1 = {pcfg.T - 1}; nothing left "
            f"to train — returning the restored final state", stacklevel=2)
        st.terminal = True
        st.hist.rounds.append(dict(
            round=start_round - 1, resumed_terminal=True,
            test_acc=evaluate(spec.module, theta[0], theta[1],
                              spec.data.x_test, spec.data.y_test,
                              pcfg.eval_batch)))
    return st


# ---------------------------------------------------------------------------
# pool schedule: deterministic up front, so the feeder can run ahead
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _BlockPlan:
    """One pool block: per-lane job index (or -1 for an idle lane), each
    active lane's starting round, and the scanned block length K = min over
    active lanes of the solo segment length (so a lane's sync rounds always
    land on the last round it executes — see ``lane_block_len``)."""
    assign: Tuple[int, ...]
    t0s: Tuple[int, ...]
    k: int


def plan_pool(states: Sequence[_JobState], order: Sequence[int], lanes: int,
              block: int) -> List[_BlockPlan]:
    """The whole pool's block schedule, computed before any round runs.
    Lane occupancy and block lengths depend only on per-job horizons and
    sync cadences — never on training outcomes — so the schedule is
    deterministic and the round feeder can assemble pool payloads ahead of
    device execution without changing any job's RNG/key consumption order."""
    from ..data.pipeline import lane_block_len
    queue = [i for i in order if not states[i].terminal]
    lane_job = [-1] * lanes
    lane_t = [0] * lanes
    for lane in range(lanes):
        if queue:
            j = queue.pop(0)
            lane_job[lane] = j
            lane_t[lane] = states[j].t
    plans: List[_BlockPlan] = []
    while any(j >= 0 for j in lane_job):
        ks = [lane_block_len(lane_t[l], states[j].pcfg.T, block,
                             states[j].is_sync)
              for l, j in enumerate(lane_job) if j >= 0]
        k = min(ks)
        plans.append(_BlockPlan(tuple(lane_job), tuple(lane_t), k))
        for lane, j in enumerate(lane_job):
            if j < 0:
                continue
            lane_t[lane] += k
            if lane_t[lane] >= states[j].pcfg.T:
                if queue:
                    nxt = queue.pop(0)
                    lane_job[lane] = nxt
                    lane_t[lane] = states[nxt].t
                else:
                    lane_job[lane] = -1
    return plans


# ---------------------------------------------------------------------------
# the pool driver
# ---------------------------------------------------------------------------

@jax.jit
def _stack_lanes(leaves):
    return jax.tree.map(lambda *ls: jnp.stack(ls), *leaves)


@jax.jit
def _stack_small_lanes(smalls):
    """Stack J lanes x K rounds of small payloads (AttackVec state, derived
    per-client keys) to a leading (J, K) in ONE dispatch — the per-lane
    eager path costs a stack dispatch per lane, which at small per-round
    compute eats the pool's amortisation win."""
    per_lane = tuple(jax.tree.map(lambda *ls: jnp.stack(ls), *s)
                     for s in smalls)
    return jax.tree.map(lambda *ls: jnp.stack(ls), *per_lane)


def _set_lane(tree_j: Pytree, lane: int, tree: Pytree) -> Pytree:
    return jax.tree.map(lambda full, leaf: full.at[lane].set(leaf),
                        tree_j, tree)


def _lane_slice(tree_j: Pytree, lane: int) -> Pytree:
    return jax.tree.map(lambda a: a[lane], tree_j)


def _replay_lane_rounds(st: _JobState, clusters_k, records, t0: int,
                        theta_lane_of, stream_snap, tel) -> None:
    """Fan one lane's slice of the pool fetch out to per-round History /
    CommMeter / telemetry / checkpoint records — the solo driver's block>1
    replay loop verbatim, so the records are bit-identical to running the
    job alone.  ``theta_lane_of()`` lazily slices the lane's theta out of
    the stacked carry (only eval/checkpoint rounds need it)."""
    pcfg, tm, spec = st.pcfg, st.tm, st.spec
    for i, brec in enumerate(records):
        t = t0 + i
        clusters = clusters_k[i]
        meter = CommMeter()
        for cluster in clusters:
            for j in range(len(cluster)):
                account_client_turn(meter, pcfg, st.d_c, st.d_cl,
                                    handoff=j < len(cluster) - 1)
        if pcfg.tamper_check:
            visited = brec["detections"] + (1 if brec["accepted"] else 0)
            account_handoff_recheck(meter, pcfg, st.d_o, st.d_c, visited)
        for _ in clusters:
            account_validation(meter, st.d_o, st.d_c)
        if brec["accepted"]:
            account_param_transfer(meter, pcfg.R * st.d_cl)
        sel_cluster = clusters[brec["selected"]]
        rec = dict(
            round=t,
            clusters=clusters,
            val_losses=brec["val_losses"],
            train_losses=brec["train_losses"],
            selected=brec["selected"],
            accepted=brec["accepted"],
            selected_honest=cluster_is_honest(sel_cluster, tm.malicious),
            honest_cluster_exists=any(
                cluster_is_honest(c, tm.malicious) for c in clusters),
            detections=brec["detections"],
            comm=dataclasses.asdict(meter),
        )
        if t % pcfg.eval_every == 0 or t == pcfg.T - 1:
            # only reachable at the pool block's last scanned round: K is
            # the min over lanes of the solo segment length, so a lane's
            # sync rounds never fall mid-block and the stacked carry holds
            # exactly this lane's post-round-t theta
            theta = theta_lane_of()
            with eval_span(tel, spec.data, t, job=spec.name):
                rec["test_acc"] = evaluate(
                    spec.module, theta[0], theta[1], spec.data.x_test,
                    spec.data.y_test, pcfg.eval_batch)
        st.hist.rounds.append(rec)
        if st.ckpt_due(t):
            from ..checkpoint import job_checkpoint_metadata, save_checkpoint
            with tel.span("round.checkpoint", round=t, job=spec.name):
                save_checkpoint(spec.checkpoint_path, theta_lane_of(),
                                job_checkpoint_metadata(t, stream_snap,
                                                        job=spec.name))
        tel.record_round(t, rec, job=spec.name)


def _run_bucket(states: List[_JobState], order: List[int], block: int,
                placement: str, lanes: Optional[int], prefetch: int,
                tel) -> None:
    """Execute one shape bucket's jobs through the shared pool program."""
    from ..checkpoint import protocol_state_metadata
    from ..data.pipeline import RoundFeeder
    from .engine import (assemble_block, put_idx, resident_data,
                         take_batches)

    runnable = [i for i in order if not states[i].terminal]
    if not runnable:
        return
    n_lanes = max(1, min(lanes if lanes else len(runnable), len(runnable)))
    plans = plan_pool(states, order, n_lanes, block)

    st0 = states[runnable[0]]
    runner = protocol_accept_runner(
        st0.spec.module, st0.pcfg.lr, placement, st0.policy,
        st0.pcfg.tamper_check, st0.pcfg.tamper_tol,
        quant=st0.pcfg.comm.quant)

    pcfg0 = st0.pcfg
    m_bar = pcfg0.M // pcfg0.R

    def _make_block(b):
        """Assemble one whole-pool block payload: each active lane's K-round
        payload in lane order, every lane consuming ITS OWN job's RNG/key
        streams exactly as the solo block path would; idle lanes copy the
        first active lane's payload as a placeholder (masked on device, no
        stream consumption).  Every lane draws its indices into a view of
        one (J, K, R, M_bar, E, B) buffer (``assemble_block(out=...)``), so
        the whole pool block pays ONE put and, when its lanes share one
        ``ClientData``, ONE device gather (else one per lane, each from its
        job's resident copy); the small leaves stack in one jitted
        dispatch.  Stream snapshots for block-end checkpoints are captured
        here, right after each lane's assembly — the fused path splits no
        keys after assembly, so this is the synchronous end-of-block stream
        state (the solo feeder argument)."""
        plan = plans[b]
        idx_j = np.empty((n_lanes, plan.k, pcfg0.R, m_bar, pcfg0.E, pcfg0.B),
                         dtype=np.int32)
        per_lane: List[Optional[tuple]] = [None] * n_lanes
        smalls: List[Optional[list]] = [None] * n_lanes
        datas: List[Optional[ClientData]] = [None] * n_lanes
        for lane, j in enumerate(plan.assign):
            if j < 0:
                continue
            st = states[j]
            st.key, clusters_k, small = assemble_block(
                st.rng, st.key, st.spec.data, st.pcfg, st.tm,
                plan.t0s[lane], plan.k, out=idx_j[lane], telemetry=tel)
            snap = None
            if st.spec.checkpoint_path is not None:
                snap = protocol_state_metadata(st.rng, st.key)
            per_lane[lane] = (clusters_k, snap)
            smalls[lane] = small
            datas[lane] = st.spec.data
        first = next(l for l, s in enumerate(smalls) if s is not None)
        for lane in range(n_lanes):
            if smalls[lane] is None:
                idx_j[lane] = idx_j[first]
                smalls[lane] = smalls[first]
                datas[lane] = datas[first]
        avec_j, keys_j = _stack_small_lanes(tuple(tuple(s) for s in smalls))
        attrs = dict(block=b, k=plan.k)
        idx_d = put_idx(idx_j, tel, **attrs)
        if all(d is datas[0] for d in datas):
            xs_j, ys_j = take_batches(datas[0], idx_d, tel, **attrs)
        else:
            xs_j, ys_j = _stack_lanes(tuple(
                take_batches(d, idx_d[lane], tel, **attrs)
                for lane, d in enumerate(datas)))
        binputs = (xs_j, ys_j, avec_j, keys_j)
        return per_lane, binputs

    for i in runnable:              # each distinct ClientData, put once
        resident_data(states[i].spec.data, tel)
    feeder = RoundFeeder(_make_block, 0, len(plans), depth=prefetch,
                         telemetry=tel)
    jobs_done = 0
    theta_j = None
    val_j = None
    prev_assign: Tuple[int, ...] = (-2,) * n_lanes
    try:
        for b, plan in enumerate(plans):
            if prefetch > 0:
                with tel.span("pool.feeder_wait", block=b,
                              depth=feeder.qsize()):
                    per_lane, binputs = feeder.get(b)
            else:
                with tel.span("block.assemble", block=b, k=plan.k):
                    per_lane, binputs = feeder.get(b)
            if plan.assign != prev_assign:
                # lane churn: (re)seat thetas and the stacked validation
                # sets.  Fresh lanes get the job's current theta; idle lanes
                # keep whatever buffer they hold (masked on device).
                if theta_j is None:
                    fill = states[next(j for j in plan.assign if j >= 0)]
                    theta_j = _stack_lanes(tuple(
                        states[j].theta if j >= 0 else fill.theta
                        for j in plan.assign))
                else:
                    for lane, j in enumerate(plan.assign):
                        if j >= 0 and prev_assign[lane] != j:
                            theta_j = _set_lane(theta_j, lane,
                                                states[j].theta)
                fill = states[next(j for j in plan.assign if j >= 0)]
                val_j = _stack_lanes(tuple(
                    (states[j].x0, states[j].y0) if j >= 0
                    else (fill.x0, fill.y0) for j in plan.assign))
                active_j = jnp.asarray([j >= 0 for j in plan.assign])
                prev_assign = plan.assign
            with tel.span("pool.step", block=b, k=plan.k,
                          active=int(np.sum([j >= 0 for j in plan.assign]))) as sp:
                theta_j, fetches = runner.pool_accept_block(
                    theta_j, binputs, val_j, active_j)
                sp.fence(fetches)
            with tel.span("pool.fetch", block=b, k=plan.k):
                fetched = np.asarray(fetches)   # the pool block's ONE sync
            for lane, j in enumerate(plan.assign):
                if j < 0:
                    continue
                st = states[j]
                clusters_k, snap = per_lane[lane]
                records = [dict(val_losses=[float(v) for v in vl],
                                train_losses=[float(v) for v in tl],
                                selected=sel, detections=det, accepted=acc)
                           for vl, tl, sel, det, acc in
                           unpack_block_fetch(fetched[lane], st.pcfg.R)]
                _replay_lane_rounds(
                    st, clusters_k, records, plan.t0s[lane],
                    lambda lane=lane: _lane_slice(theta_j, lane), snap, tel)
                st.t = plan.t0s[lane] + plan.k
                if st.t >= st.pcfg.T:
                    st.theta = _lane_slice(theta_j, lane)
                    jobs_done += 1
            t0s = {states[j].spec.name: plan.t0s[lane]
                   for lane, j in enumerate(plan.assign) if j >= 0}
            tel.emit({"event": "pool_block", "block": b,
                      **pool_gauges(t0s, plan.k, n_lanes, jobs_done,
                                    len(runnable))})
    finally:
        feeder.close()


def run_job_pool(specs: Sequence[JobSpec], *, block: int = 1,
                 placement: str = "vmap", lanes: Optional[int] = None,
                 prefetch: int = 0, telemetry=None,
                 verbose: bool = False) -> Dict[str, History]:
    """Run a pool of Pigeon-SL jobs through shared megabatched device
    programs.  Returns ``{spec.name: History}`` with every job's History
    bit-identical to a solo ``run_pigeon(engine="batched")`` of the same
    spec.

    * ``block`` — rounds fused per device dispatch, per lane (the solo
      ``block=`` knob); each pool block scans ``K = min`` over its active
      lanes' solo segment lengths, so per-lane eval/checkpoint cadences are
      honoured exactly.
    * ``lanes`` — device lanes per bucket (default: one per job).  With
      fewer lanes than jobs, finished jobs free their lane and the queue
      refills it between blocks (elastic recycling).
    * ``placement`` — ``"vmap"`` stacks lanes on one device; ``"sharded"``
      lays the JOB axis over a 1-D device mesh (jobs are embarrassingly
      parallel — no collectives).
    * ``prefetch`` — assemble pool block b+1 on a background thread while
      block b executes (the pool schedule is deterministic up front, so the
      feeder preserves every job's RNG/key order).
    """
    pool = JobPool(specs, block=block, placement=placement)
    tel = resolve_telemetry(telemetry, verbose=verbose, run="pool",
                            jobs=len(specs), block=block,
                            placement=placement, lanes=lanes or 0,
                            buckets=len(pool.buckets()))
    try:
        states: Dict[int, _JobState] = {}
        for bucket in pool.buckets():
            bucket_states: List[_JobState] = []
            for i in bucket:
                policy, tm, pcfg = pool.resolved(i)
                states[i] = _init_job(pool.specs[i], policy, tm, pcfg)
                bucket_states.append(states[i])
            all_states = [states[i] for i in bucket]
            _run_bucket(all_states, list(range(len(all_states))), block,
                        placement, lanes, prefetch, tel)
    finally:
        tel.close()
    return {pool.specs[i].name: states[i].hist for i in
            sorted(states, key=lambda i: i)}
