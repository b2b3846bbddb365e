"""Host-side reference selector: the Section III-C acceptance loop.

This is the argmin/tamper-check/rollback cascade that used to live inline in
``core/protocol.py::run_pigeon``, lifted verbatim and generalised over a
:class:`~repro.selection.policies.SelectionPolicy`.  It remains the
*reference* execution form — the sequential oracle always runs it, and the
batched engine falls back to it whenever the threat model contains
param-tampering families (the handoff tampering and its key splits are
host-side by design: the number of key splits depends on which candidates
the cascade visits, which the fused on-device cascade cannot reproduce
without a sync).  The default batched path runs the equivalent fused cascade
compiled into the round program (``repro.selection.cascade`` via
``RoundRunner.accept``); the equivalence suite pins the two together.

Bit-compatibility: with the default argmin policy this function consumes the
numpy/JAX streams, mutates the CommMeter and walks candidates exactly as the
pre-refactor inline loop did.
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .policies import ScoreContext, SelectionPolicy

# NOTE: repro.core imports are deferred into the function bodies —
# core/protocol.py imports this subsystem at module level, so importing the
# core package here would be circular.

Pytree = Any


@dataclasses.dataclass
class SelectionOutcome:
    """One round's acceptance verdict, identical in content to the fused
    cascade's single fetch (``repro.selection.cascade.pack_fetch``)."""
    selected: int
    accepted: bool
    detections: int
    theta: Tuple[Pytree, Pytree]
    scores: np.ndarray


def effective_shards(k: int, d_o: int) -> int:
    """Largest divisor of D_o at most ``k`` — the shard count both the host
    and fused median-of-means paths actually use (one shared divisor rule:
    ``repro.kernels.ops.largest_divisor``)."""
    from ..kernels.ops import largest_divisor
    return largest_divisor(d_o, k)


@lru_cache(maxsize=None)
def _shard_loss_fn(module, k: int):
    """Jitted (phi, vacts, y0) -> (k,) per-shard shared-set losses — the
    same shard arithmetic the fused specs compile
    (``repro.core.runner.sharded_validation_losses``), applied to the
    validation-time activations the cluster already pushed."""
    from ..core.runner import sharded_validation_losses

    @jax.jit
    def f(phi, vacts, y0):
        return sharded_validation_losses(module, phi, vacts, y0, k)

    return f


def _result_vacts(module, res: Dict[str, Any], x0):
    """A result's validation-time activations, recomputed from the cluster
    params when the round body dropped them (SplitFed's batched rounds keep
    val_aux None — there is no tamper check to feed)."""
    from ..core.protocol import res_params, res_vacts
    stacked = res.get("_stacked")
    if "vacts" in res or (stacked is not None and stacked[2] is not None):
        return res_vacts(res)
    from ..core.validation import handoff_activations
    return handoff_activations(module, res_params(res)[0], x0)


def host_score_context(policy: SelectionPolicy, module,
                       results: List[Dict[str, Any]], x0, y0) -> ScoreContext:
    """Assemble the policy's feature context from host-side round results.
    Results carry ``vloss`` always, ``msg_stats`` when the round was trained
    with message statistics, and validation activations (``res_vacts``, or
    recomputed from the cluster params) for the shard-loss feature."""
    from ..core.protocol import res_params
    vlosses = jnp.asarray(np.asarray([res["vloss"] for res in results],
                                     dtype=np.float32))
    shard_losses = None
    if policy.shard_count > 0:
        x0, y0 = jnp.asarray(x0), jnp.asarray(y0)
        k = effective_shards(policy.shard_count, int(y0.shape[0]))
        fn = _shard_loss_fn(module, k)
        shard_losses = jnp.stack([
            fn(res_params(res)[1], _result_vacts(module, res, x0), y0)
            for res in results])
    message_stats = None
    if policy.needs_message_stats:
        message_stats = jnp.asarray(np.stack(
            [np.asarray(res["msg_stats"]) for res in results]))
    return ScoreContext(vlosses=vlosses, shard_losses=shard_losses,
                        message_stats=message_stats)


def score_and_rank(policy: SelectionPolicy, ctx: ScoreContext
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(scores, eligibility, visit order).  The float64 cast before argsort
    reproduces the pre-refactor host loop bit-for-bit under the argmin
    policy (it sorted the python-float loss list, i.e. a float64 array)."""
    scores = np.asarray(policy.score(ctx), dtype=np.float64)
    elig = np.asarray(policy.eligible(ctx, jnp.asarray(scores,
                                                       dtype=jnp.float32)))
    if not elig.any():
        elig = np.ones_like(elig)
    order = np.argsort(scores)
    return scores, elig, order


def select_host(policy: SelectionPolicy, module, results: List[Dict[str, Any]],
                theta: Tuple[Pytree, Pytree], tm, t: int, key: jax.Array,
                pcfg, meter, x0, y0, d_c: int
                ) -> Tuple[jax.Array, SelectionOutcome]:
    """The reference cascade: rank by policy score, visit candidates in
    order, tamper-check each handoff (rolling the protocol key exactly when
    the visited candidate's last client mounts a handoff attack), commit the
    first survivor.  Mutates ``meter`` with the per-visit re-transmission
    accounting (Table I's 2R*D_o validation term)."""
    from ..core import attacks as atk
    from ..core.protocol import (account_handoff_recheck, res_params,
                                 res_vacts)
    from ..core.validation import check_handoff, handoff_activations
    ctx = host_score_context(policy, module, results, x0, y0)
    scores, elig, order = score_and_rank(policy, ctx)
    d_o = int(x0.shape[0])

    detection_events = 0
    selected: Optional[int] = None
    new_theta = theta
    for cand in order:
        if not elig[cand]:
            continue                  # trimmed outlier: never visited
        res = results[cand]
        last_client = res["cluster"][-1]
        g_sel, p_sel = res_params(res)
        handed = g_sel
        pt = tm.param_attack_for(last_client, t)
        if pt is not None:
            key, sub = jax.random.split(key)
            handed = atk.tamper_params(pt, g_sel, sub)
        if pcfg.tamper_check:
            # next-round first clients re-transmit g(x0, gamma_received);
            # >=1 of the R recipients is honest, so a tampered handoff is
            # always visible against the validation-time activations.
            recv = handoff_activations(module, handed, x0)
            account_handoff_recheck(meter, pcfg, d_o, d_c, visited=1)
            ok, dist = check_handoff(res_vacts(res), [recv], pcfg.tamper_tol)
            if not ok:
                detection_events += 1
                continue              # discard tampered cluster, reselect
        selected = int(cand)
        new_theta = (handed, p_sel)
        break

    accepted = selected is not None
    if not accepted:                  # every candidate tampered: keep theta^t
        selected = int(next(c for c in order if elig[c]))
        new_theta = theta
    return key, SelectionOutcome(selected=selected, accepted=accepted,
                                 detections=detection_events,
                                 theta=new_theta,
                                 scores=scores.astype(np.float32))
