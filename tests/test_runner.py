"""RoundRunner placements + double-buffered host pipeline.

Equivalence contract: the sharded placement (cluster axis laid over a
("pod",) host mesh via shard_map) must reproduce the vmap placement and the
sequential oracle — same selection every round, validation losses within
float tolerance, bit-identical CommMeter counts — and the prefetching
RoundFeeder must leave the trajectory bit-identical to synchronous assembly.

The sharded tests run at any device count (the runner sizes the mesh to the
largest divisor of R that fits); the multi-device assertions only engage
under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` — CI runs this
file a second time under that flag so the shard_map path cannot rot.
"""
import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (HONEST, LABEL_FLIP, Attack, ProtocolConfig,
                        run_pigeon, run_pigeon_plus, run_pigeon_sweep,
                        run_splitfed)
from repro.core.engine import assemble_round_batches, sample_batch_idx
from repro.core.runner import (PLACEMENTS, RoundRunner, RoundSpec,
                               backend_supports_partial_auto, cluster_map,
                               cluster_mesh, onehot_select, sweep_map,
                               sweep_mesh)
from repro.data.pipeline import RoundFeeder

multi_device = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
           "(the dedicated CI multi-device step sets it)")


def assert_histories_equivalent(h_a, h_b, exact=False):
    assert len(h_a.rounds) == len(h_b.rounds)
    for ra, rb in zip(h_a.rounds, h_b.rounds):
        assert ra["clusters"] == rb["clusters"]
        assert ra["selected"] == rb["selected"], (ra["round"], ra, rb)
        assert ra["comm"] == rb["comm"]          # bit-identical float counts
        if exact:
            assert ra["val_losses"] == rb["val_losses"]
            assert ra.get("test_acc") == rb.get("test_acc")
        else:
            np.testing.assert_allclose(ra["val_losses"], rb["val_losses"],
                                       rtol=2e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# sharded placement vs vmap placement vs sequential oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("malicious,attack", [(set(), HONEST),
                                              ({1}, Attack(LABEL_FLIP))],
                         ids=["honest", "label_flip"])
def test_sharded_matches_vmap(tiny_task, tiny_pcfg, malicious, attack):
    data, module = tiny_task
    h_v = run_pigeon(module, data, tiny_pcfg, malicious=malicious,
                     attack=attack, engine="batched", placement="vmap")
    h_s = run_pigeon(module, data, tiny_pcfg, malicious=malicious,
                     attack=attack, engine="batched", placement="sharded")
    assert_histories_equivalent(h_v, h_s)


def test_sharded_matches_sequential_oracle(tiny_task, tiny_pcfg):
    data, module = tiny_task
    h_seq = run_pigeon(module, data, tiny_pcfg, malicious={1},
                       attack=Attack(LABEL_FLIP), engine="sequential")
    h_s = run_pigeon(module, data, tiny_pcfg, malicious={1},
                     attack=Attack(LABEL_FLIP), engine="batched",
                     placement="sharded")
    assert_histories_equivalent(h_seq, h_s)


def test_placement_validation(tiny_task, tiny_pcfg):
    data, module = tiny_task
    with pytest.raises(ValueError, match="placement"):
        run_pigeon(module, data, tiny_pcfg, engine="batched", placement="warp")
    with pytest.raises(ValueError, match="batched"):
        run_pigeon(module, data, tiny_pcfg, engine="sequential",
                   placement="sharded")
    with pytest.raises(ValueError, match="batched"):
        run_pigeon(module, data, tiny_pcfg, engine="sequential", prefetch=1)
    assert PLACEMENTS == ("vmap", "sharded")


@multi_device
def test_cluster_mesh_uses_multiple_devices():
    """R=4 on the forced 8-device host must land on a real 4-way pod mesh
    (largest divisor of R that fits), not silently collapse to one device."""
    mesh = cluster_mesh(4)
    assert mesh.shape["pod"] == 4
    assert cluster_mesh(3).shape["pod"] in (1, 3)
    assert cluster_mesh(16).shape["pod"] == jax.device_count()


@multi_device
def test_sharded_multi_device_matches_oracle(tiny_task):
    """True multi-device run: R=4 clusters over a 4-device pod mesh, checked
    against the sequential oracle (selection + losses + comm)."""
    data, module = tiny_task
    pcfg = ProtocolConfig(M=4, N=3, T=2, E=2, B=16, lr=0.05, seed=0)
    h_seq = run_pigeon(module, data, pcfg, malicious={1},
                       attack=Attack(LABEL_FLIP), engine="sequential")
    h_s = run_pigeon(module, data, pcfg, malicious={1},
                     attack=Attack(LABEL_FLIP), engine="batched",
                     placement="sharded")
    assert_histories_equivalent(h_seq, h_s)


@multi_device
def test_runner_round_selects_and_broadcasts_across_devices():
    """The in-program selection path (round_fn) on a real pod mesh: winner
    broadcast must equalise every cluster slot."""
    spec = RoundSpec(
        train_cluster=lambda p, b: (jax.tree.map(lambda w: w - 0.1 * b.mean(), p),
                                    b.mean()),
        validate=lambda p, val: (jnp.mean((p["w"] - val) ** 2), None))
    runner = RoundRunner(spec, placement="sharded", params_stacked=True)
    r = 4
    stacked = {"w": jnp.arange(float(r * 3)).reshape(r, 3)}
    batches = jnp.ones((r, 2)) * jnp.arange(float(r))[:, None]
    rebro, vlosses, sel = runner.round(stacked, batches, jnp.zeros(3))
    assert vlosses.shape == (r,)
    assert int(sel) == int(np.argmin(np.asarray(vlosses)))
    for i in range(1, r):
        np.testing.assert_allclose(np.asarray(rebro["w"][0]),
                                   np.asarray(rebro["w"][i]))
    # must match the vmap placement bit-for-bit on CPU
    runner_v = RoundRunner(spec, placement="vmap", params_stacked=True)
    rebro_v, vlosses_v, sel_v = runner_v.round(stacked, batches, jnp.zeros(3))
    np.testing.assert_array_equal(np.asarray(vlosses), np.asarray(vlosses_v))
    np.testing.assert_array_equal(np.asarray(rebro["w"]),
                                  np.asarray(rebro_v["w"]))


# ---------------------------------------------------------------------------
# SplitFed placements (FedAvg combine hook) + sweep placements (2-D mesh)
# ---------------------------------------------------------------------------

def assert_selection_histories_equivalent(h_a, h_b, exact=False):
    """SplitFed records carry (selected, val_losses, selected_honest,
    test_acc) but no clusters/comm — compare what both have."""
    assert len(h_a.rounds) == len(h_b.rounds)
    for ra, rb in zip(h_a.rounds, h_b.rounds):
        assert ra["selected"] == rb["selected"], (ra["round"], ra, rb)
        assert ra["selected_honest"] == rb["selected_honest"]
        if exact:
            assert ra["val_losses"] == rb["val_losses"]
            assert ra.get("test_acc") == rb.get("test_acc")
        else:
            np.testing.assert_allclose(ra["val_losses"], rb["val_losses"],
                                       rtol=2e-5, atol=1e-6)


@pytest.mark.parametrize("malicious,attack", [(set(), HONEST),
                                              ({1}, Attack(LABEL_FLIP))],
                         ids=["honest", "label_flip"])
def test_splitfed_placements_match_sequential_oracle(tiny_task, tiny_pcfg,
                                                     malicious, attack):
    data, module = tiny_task
    h_seq = run_splitfed(module, data, tiny_pcfg, malicious=malicious,
                         attack=attack, engine="sequential")
    for placement in PLACEMENTS:
        h = run_splitfed(module, data, tiny_pcfg, malicious=malicious,
                         attack=attack, engine="batched", placement=placement)
        assert_selection_histories_equivalent(h_seq, h)


def test_splitfed_prefetch_bit_identical(tiny_task, tiny_pcfg):
    """SplitFed sampling never depends on selection, so the feeder runs at
    full depth and the trajectory must equal prefetch=0 bit-for-bit — under
    both placements."""
    data, module = tiny_task
    h_sync = run_splitfed(module, data, tiny_pcfg, malicious={1},
                          attack=Attack(LABEL_FLIP), engine="batched")
    h_pre = run_splitfed(module, data, tiny_pcfg, malicious={1},
                         attack=Attack(LABEL_FLIP), engine="batched",
                         prefetch=2)
    assert_selection_histories_equivalent(h_sync, h_pre, exact=True)
    h_pre_sharded = run_splitfed(module, data, tiny_pcfg, malicious={1},
                                 attack=Attack(LABEL_FLIP), engine="batched",
                                 placement="sharded", prefetch=1)
    assert_selection_histories_equivalent(h_sync, h_pre_sharded)


def test_splitfed_placement_validation(tiny_task, tiny_pcfg):
    data, module = tiny_task
    with pytest.raises(ValueError, match="placement"):
        run_splitfed(module, data, tiny_pcfg, engine="batched",
                     placement="warp")
    with pytest.raises(ValueError, match="batched"):
        run_splitfed(module, data, tiny_pcfg, engine="sequential",
                     placement="sharded")
    with pytest.raises(ValueError, match="batched"):
        run_splitfed(module, data, tiny_pcfg, engine="sequential", prefetch=1)


def test_combine_hook_applies_before_validation():
    """RoundSpec.combine (SplitFed's FedAvg fan-in) must transform the
    per-client stack into the cluster model the validator sees."""
    spec = RoundSpec(
        train_cluster=lambda p, b: (p + b, b.sum(axis=-1)),   # (M_bar,) out
        validate=lambda p, val: (jnp.abs(p - val), None),
        combine=lambda p: jnp.mean(p, axis=0))
    params = jnp.float32(1.0)
    inputs = jnp.arange(6.0).reshape(2, 3)        # R=2 clusters, M_bar=3
    new_p, aux, vl, _ = cluster_map(spec, params, inputs, jnp.float32(0.0))
    np.testing.assert_allclose(np.asarray(new_p), [2.0, 5.0])   # mean(1 + b)
    np.testing.assert_allclose(np.asarray(vl), [2.0, 5.0])
    for placement in PLACEMENTS:
        c = RoundRunner(spec, placement=placement).candidates(
            params, inputs, jnp.float32(0.0))
        np.testing.assert_array_equal(np.asarray(c[0]), np.asarray(new_p))


def test_sweep_sharded_matches_vmap(tiny_task, tiny_pcfg):
    """The 2-D (seed, cluster) placement must reproduce the vmap sweep —
    same per-seed selections and losses, every round."""
    data, module = tiny_task
    h_v = run_pigeon_sweep(module, data, tiny_pcfg, malicious={1},
                           attack=Attack(LABEL_FLIP), seeds=(0, 1))
    h_s = run_pigeon_sweep(module, data, tiny_pcfg, malicious={1},
                           attack=Attack(LABEL_FLIP), seeds=(0, 1),
                           placement="sharded")
    assert len(h_v) == len(h_s) == 2
    for h_a, h_b in zip(h_v, h_s):
        assert len(h_a.rounds) == len(h_b.rounds)
        for ra, rb in zip(h_a.rounds, h_b.rounds):
            assert ra["clusters"] == rb["clusters"]
            assert ra["selected"] == rb["selected"]
            assert ra["comm"] == rb["comm"]
            np.testing.assert_allclose(ra["val_losses"], rb["val_losses"],
                                       rtol=2e-5, atol=1e-6)


def test_sweep_map_selects_per_seed():
    """Unit check of the sweep body: per-seed argmin + winner carry."""
    spec = RoundSpec(
        train_cluster=lambda p, b: (p + b.sum(), b.sum()),
        validate=lambda p, val: (jnp.abs(p - val), None))
    params = jnp.array([0.0, 10.0])                     # S=2 seeds
    inputs = jnp.array([[[1.0], [4.0]], [[2.0], [3.0]]])  # (S=2, R=2, 1)
    winners, aux, vlosses, sels = sweep_map(spec, params, inputs,
                                            jnp.float32(5.0))
    # seed 0: candidates 1, 4 -> |1-5|=4 vs |4-5|=1 -> cluster 1 wins (4.0)
    # seed 1: candidates 12, 13 -> 7 vs 8 -> cluster 0 wins (12.0)
    np.testing.assert_array_equal(np.asarray(sels), [1, 0])
    np.testing.assert_allclose(np.asarray(winners), [4.0, 12.0])
    assert vlosses.shape == (2, 2)


@multi_device
def test_sweep_mesh_factorisation():
    """On the forced 8-device host the sweep mesh must cover as many devices
    as (divisor of S) x (divisor of R) allows."""
    assert dict(sweep_mesh(2, 4).shape) == {"seed": 2, "pod": 4}
    assert dict(sweep_mesh(2, 2).shape) == {"seed": 2, "pod": 2}
    assert dict(sweep_mesh(3, 4).shape) == {"seed": 3, "pod": 2}
    assert dict(sweep_mesh(1, 16).shape) == {"seed": 1, "pod": 8}


def test_largest_divisor_properties():
    """_largest_divisor(n, cap): a divisor of n, <= cap, >= 1 — including
    degenerate caps (0, negative) and prime n, where it must degrade to 1
    rather than divide by zero."""
    from repro.core.runner import _largest_divisor
    for n in range(1, 25):
        for cap in range(-2, 25):
            d = _largest_divisor(n, cap)
            assert d >= 1 and n % d == 0
            assert cap < 1 or d <= cap
            # maximality: no larger divisor fits the cap
            assert not any(n % e == 0 for e in range(d + 1,
                                                     max(cap, 1) + 1))


@pytest.mark.parametrize("devices", [1, 2, 3, 5, 7, 8, 12])
def test_sweep_mesh_packing_properties(devices):
    """Property grid over (S, R, device-count), emulated via max_devices:
    the (seed, pod) factorisation always divides (S, R), fits the device
    budget, and never covers fewer devices than the widest 1-D cluster mesh
    — prime/non-factoring S and R (e.g. 7 x 11 on 8 devices) must fall back
    to the 1-D cluster mesh, not collapse to a 1x1 grid."""
    from repro.core.runner import _largest_divisor
    budget = min(devices, jax.device_count())
    for s in (1, 2, 3, 4, 5, 7, 11):
        for r in (1, 2, 3, 4, 6, 7, 11, 13):
            shape = dict(sweep_mesh(s, r, max_devices=devices).shape)
            sn, rn = shape["seed"], shape["pod"]
            assert s % sn == 0 and r % rn == 0
            assert 1 <= sn * rn <= budget
            one_d = dict(cluster_mesh(r, max_devices=devices).shape)["pod"]
            assert one_d == _largest_divisor(r, budget)
            assert sn * rn >= one_d, (s, r, devices)


@multi_device
def test_sweep_sharded_multi_device_matches_vmap(tiny_task):
    """S x R = 2 x 2 replicas over a real (2, 2) device mesh."""
    data, module = tiny_task
    pcfg = ProtocolConfig(M=4, N=1, T=2, E=2, B=16, lr=0.05, seed=0)
    h_v = run_pigeon_sweep(module, data, pcfg, malicious={1},
                           attack=Attack(LABEL_FLIP), seeds=(0, 1))
    h_s = run_pigeon_sweep(module, data, pcfg, malicious={1},
                           attack=Attack(LABEL_FLIP), seeds=(0, 1),
                           placement="sharded")
    for h_a, h_b in zip(h_v, h_s):
        for ra, rb in zip(h_a.rounds, h_b.rounds):
            assert ra["selected"] == rb["selected"]
            np.testing.assert_allclose(ra["val_losses"], rb["val_losses"],
                                       rtol=2e-5, atol=1e-6)


@multi_device
def test_splitfed_sharded_multi_device_matches_oracle(tiny_task):
    """R=4 SplitFed clusters over a 4-device pod mesh vs the sequential
    oracle."""
    data, module = tiny_task
    pcfg = ProtocolConfig(M=4, N=3, T=2, E=2, B=16, lr=0.05, seed=0)
    h_seq = run_splitfed(module, data, pcfg, malicious={1},
                         attack=Attack(LABEL_FLIP), engine="sequential")
    h_s = run_splitfed(module, data, pcfg, malicious={1},
                       attack=Attack(LABEL_FLIP), engine="batched",
                       placement="sharded")
    assert_selection_histories_equivalent(h_seq, h_s)


# ---------------------------------------------------------------------------
# CPU backend gate for partial-auto meshes (ROADMAP open item)
# ---------------------------------------------------------------------------

@multi_device
def test_partial_auto_cpu_gate_raises_clear_error():
    """A mesh with GSPMD-auto axes of size > 1 on CPU cannot execute (XLA has
    no PartitionId under SPMD there) — the runner must refuse with a clear
    error at the execution entry instead of letting XLA crash.  The same
    mesh stays usable for dry-run lowering (gate-free ``*_fn`` bodies)."""
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("pod", "data"))
    assert not backend_supports_partial_auto(mesh, ("pod",))
    spec = RoundSpec(train_cluster=lambda p, b: (p, b),
                     validate=lambda p, v: (jnp.float32(0), None))
    runner = RoundRunner(spec, placement="sharded", mesh=mesh)
    with pytest.raises(RuntimeError, match="partial-auto.*CPU"):
        runner.round(jnp.zeros(()), jnp.zeros((4, 2)), jnp.zeros(()))
    with pytest.raises(RuntimeError, match="partial-auto.*CPU"):
        runner.candidates(jnp.zeros(()), jnp.zeros((4, 2)), jnp.zeros(()))
    # fully-manual meshes (no auto axes) stay allowed on CPU
    manual = Mesh(np.array(jax.devices()[:2]), ("pod",))
    assert backend_supports_partial_auto(manual, ("pod",))
    # lowering the same partial-auto program is still supported
    jax.jit(runner.round_fn()).lower(
        jnp.zeros(()), jnp.zeros((4, 2)), jnp.zeros(()))


def test_sharded_rejects_indivisible_mesh(tiny_task):
    """An explicit mesh whose pod axis does not divide R must be refused,
    not silently mis-sharded."""
    from jax.sharding import Mesh
    spec = RoundSpec(train_cluster=lambda p, b: (p, b),
                     validate=lambda p, v: (jnp.float32(0), None))
    mesh = Mesh(np.array(jax.devices()[:1]), ("pod",))
    runner = RoundRunner(spec, placement="sharded", mesh=mesh)
    if jax.device_count() < 2:
        pytest.skip("cannot build an indivisible mesh on one device")
    mesh2 = Mesh(np.array(jax.devices()[:2]), ("pod",))
    runner2 = RoundRunner(spec, placement="sharded", mesh=mesh2)
    with pytest.raises(ValueError, match="divisible"):
        runner2.round(jnp.zeros(()), jnp.zeros((3, 2)), jnp.zeros(()))


# ---------------------------------------------------------------------------
# double-buffered host pipeline
# ---------------------------------------------------------------------------

def test_prefetch_history_bit_identical(tiny_task, tiny_pcfg):
    """The feeder consumes the numpy RNG and JAX key stream in exactly the
    synchronous order, so prefetch on/off trajectories are bit-identical —
    same floats, not merely within tolerance."""
    data, module = tiny_task
    h_sync = run_pigeon(module, data, tiny_pcfg, malicious={1},
                        attack=Attack(LABEL_FLIP), engine="batched")
    h_pre = run_pigeon(module, data, tiny_pcfg, malicious={1},
                       attack=Attack(LABEL_FLIP), engine="batched", prefetch=1)
    assert_histories_equivalent(h_sync, h_pre, exact=True)
    h_pre2 = run_pigeon(module, data, tiny_pcfg, malicious={1},
                        attack=Attack(LABEL_FLIP), engine="batched",
                        prefetch=2, placement="sharded")
    assert_histories_equivalent(h_sync, h_pre2, exact=True)


def test_prefetch_plus_phase_boundary_fallback(tiny_task, tiny_pcfg):
    """Pigeon-SL+ sub-rounds sample the *selected* cluster, so the feeder
    must bound its depth to zero — prefetch is accepted but the trajectory
    equals the synchronous one."""
    data, module = tiny_task
    h_sync = run_pigeon_plus(module, data, tiny_pcfg, malicious={1},
                             attack=Attack(LABEL_FLIP), engine="batched")
    h_pre = run_pigeon_plus(module, data, tiny_pcfg, malicious={1},
                            attack=Attack(LABEL_FLIP), engine="batched",
                            prefetch=2)
    assert_histories_equivalent(h_sync, h_pre, exact=True)


def test_round_feeder_orders_and_bounds():
    produced = []

    def make_round(t):
        produced.append(t)
        return t * 10

    feeder = RoundFeeder(make_round, 0, 6, depth=1)
    try:
        for t in range(6):
            assert feeder.get(t) == t * 10
    finally:
        feeder.close()
    assert produced == list(range(6))       # strictly ascending — RNG order


def test_round_feeder_rejects_out_of_order_and_propagates_errors():
    def boom(t):
        if t == 1:
            raise RuntimeError("assembly failed")
        return t

    feeder = RoundFeeder(boom, 0, 3, depth=2)
    try:
        assert feeder.get(0) == 0
        with pytest.raises(RuntimeError, match="assembly failed"):
            feeder.get(1)
    finally:
        feeder.close()

    feeder = RoundFeeder(lambda t: t, 0, 3, depth=1)
    try:
        with pytest.raises(RuntimeError, match="out of order"):
            feeder.get(2)
    finally:
        feeder.close()


def test_round_feeder_close_unblocks_producer():
    started = threading.Event()

    def make_round(t):
        started.set()
        return t

    feeder = RoundFeeder(make_round, 0, 1000, depth=1)
    started.wait(timeout=5)
    feeder.close()                           # producer blocked on a full queue
    feeder.close()                           # idempotent
    assert feeder._thread is None


def test_round_feeder_depth_zero_is_synchronous():
    calls = []
    feeder = RoundFeeder(lambda t: calls.append(t) or t, 0, 4, depth=0)
    assert feeder.get(0) == 0
    assert calls == [0]                      # nothing assembled ahead
    assert feeder.get(1) == 1
    feeder.close()


# ---------------------------------------------------------------------------
# single-copy round assembly
# ---------------------------------------------------------------------------

def test_assemble_round_batches_matches_reference(tiny_task, tiny_pcfg):
    """The device gather from the resident shards must consume the RNG
    identically to the historical host path and produce, bit for bit, the
    per-client ``data.x[client][idx]`` arrays, on the device."""
    data, _ = tiny_task
    clusters = [[0, 1], [2, 3]]
    xs, ys = assemble_round_batches(np.random.default_rng(7), data, clusters,
                                    tiny_pcfg)

    rng = np.random.default_rng(7)
    xs_ref, ys_ref = [], []
    for cluster in clusters:
        xs_c, ys_c = [], []
        for client in cluster:
            idx = sample_batch_idx(rng, data.x[client].shape[0],
                                   tiny_pcfg.E, tiny_pcfg.B)
            xs_c.append(data.x[client][idx])
            ys_c.append(data.y[client][idx])
        xs_ref.append(np.stack(xs_c))
        ys_ref.append(np.stack(ys_c))
    assert isinstance(xs, jax.Array) and isinstance(ys, jax.Array)
    np.testing.assert_array_equal(np.asarray(xs), np.stack(xs_ref))
    np.testing.assert_array_equal(np.asarray(ys), np.stack(ys_ref))
    assert xs.shape == (2, 2, tiny_pcfg.E, tiny_pcfg.B) + data.x.shape[2:]
    assert (xs.dtype, ys.dtype) == (data.x.dtype, data.y.dtype)


def _host_rounds(data, pcfg, split_keys):
    """``pcfg.T`` rounds as the host assembled them before the device
    gather: the same cluster and index draws, each client's batches taken
    with ``np.take`` from its shard; the per-client keys; and where both
    randomness streams end."""
    from repro.checkpoint import protocol_state_metadata
    from repro.core.clustering import make_clusters
    rng = np.random.default_rng(pcfg.seed)
    key, _ = jax.random.split(jax.random.PRNGKey(pcfg.seed))
    m_bar = pcfg.M // pcfg.R
    rounds = []
    for _ in range(pcfg.T):
        clusters = make_clusters(rng, pcfg.M, pcfg.R)
        xs = np.empty((pcfg.R, m_bar, pcfg.E, pcfg.B) + data.x.shape[2:],
                      dtype=data.x.dtype)
        ys = np.empty((pcfg.R, m_bar, pcfg.E, pcfg.B) + data.y.shape[2:],
                      dtype=data.y.dtype)
        for i, cluster in enumerate(clusters):
            for j, client in enumerate(cluster):
                idx = sample_batch_idx(rng, data.x.shape[1], pcfg.E, pcfg.B)
                np.take(data.x[client], idx, axis=0, out=xs[i, j])
                np.take(data.y[client], idx, axis=0, out=ys[i, j])
        key, keys = split_keys(key, clusters)
        rounds.append((xs, ys, np.asarray(keys)))
    return rounds, protocol_state_metadata(rng, key)


@pytest.mark.parametrize("path",
                         ["feeder", "inline", "block", "splitfed", "pool"])
def test_device_gather_matches_host_reference(tiny_task, path, monkeypatch,
                                              tmp_path):
    """Every batched assembler — the feeder, in line, a round block,
    SplitFed and a job pool whose lanes read different ClientData — hands
    the round program exactly the batches the host ``np.take`` path built,
    with the same keys, and leaves both randomness streams where that path
    left them (the last round's checkpoint holds them)."""
    from repro.checkpoint import load_checkpoint
    from repro.core.engine import round_client_keys, splitfed_keys
    from repro.core.jobs import JobSpec, run_job_pool

    data, module = tiny_task
    pcfg = ProtocolConfig(M=4, N=1, T=4, E=2, B=16, lr=0.05, seed=3,
                          eval_every=4)
    seen = []
    for name in ("accept", "accept_block", "pool_accept_block"):
        def entry(self, params, inputs, *rest, _orig=getattr(RoundRunner,
                                                              name)):
            seen.append(tuple(np.asarray(inputs[i]) for i in (0, 1, 3)))
            return _orig(self, params, inputs, *rest)
        monkeypatch.setattr(RoundRunner, name, entry)

    def rounds_of(blocks):          # a leading block axis -> one per round
        return [tuple(a[i] for a in b) for b in blocks
                for i in range(b[0].shape[0])]

    ck = str(tmp_path / "ck")
    if path == "pool":
        other = dataclasses.replace(data, x=data.x[::-1].copy(),
                                    y=data.y[::-1].copy())
        datas = [data, other]
        run_job_pool([JobSpec(name=f"job{i}", module=module, data=d,
                              pcfg=dataclasses.replace(pcfg, seed=i),
                              checkpoint_path=f"{ck}{i}", checkpoint_every=4)
                      for i, d in enumerate(datas)], block=2, prefetch=1)
        cases = [(d, dataclasses.replace(pcfg, seed=i),
                  rounds_of([tuple(a[i] for a in b) for b in seen]),
                  f"{ck}{i}") for i, d in enumerate(datas)]
    elif path == "splitfed":
        run_splitfed(module, data, pcfg, engine="batched", prefetch=1)
        cases = [(data, pcfg, seen, None)]
    else:
        run_pigeon(module, data, pcfg, engine="batched",
                   prefetch=1 if path == "feeder" else 0,
                   block=2 if path == "block" else 1,
                   checkpoint_path=ck, checkpoint_every=4)
        cases = [(data, pcfg, rounds_of(seen) if path == "block" else seen,
                  ck)]
    split = splitfed_keys if path == "splitfed" else round_client_keys
    for d, p, got, ckpt in cases:
        want, streams = _host_rounds(d, p, split)
        assert len(got) == len(want) == p.T
        for g, w in zip(got, want):
            for a, b in zip(g, w):
                np.testing.assert_array_equal(a, b)
        if ckpt is not None:
            _, meta = load_checkpoint(ckpt)
            assert meta["round"] == p.T - 1
            assert meta["rng_state"] == streams["rng_state"]
            assert meta["key"] == streams["key"]


# ---------------------------------------------------------------------------
# one source of truth: the launch adapter runs the same round body
# ---------------------------------------------------------------------------

def test_cluster_map_is_shared_by_both_layers():
    """A toy RoundSpec run through cluster_map, the vmap runner and the
    sharded runner must agree bit-for-bit — there is only one round body."""
    spec = RoundSpec(
        train_cluster=lambda p, b: (p + b.sum(), b.sum()),
        validate=lambda p, val: (jnp.abs(p - val), p * 2))
    params = jnp.float32(1.0)
    inputs = jnp.arange(6.0).reshape(3, 2)
    val = jnp.float32(5.0)
    new_p, aux, vl, vaux = cluster_map(spec, params, inputs, val)
    for placement in PLACEMENTS:
        runner = RoundRunner(spec, placement=placement)
        c = runner.candidates(params, inputs, val)
        np.testing.assert_array_equal(np.asarray(c[0]), np.asarray(new_p))
        np.testing.assert_array_equal(np.asarray(c[2]), np.asarray(vl))
        np.testing.assert_array_equal(np.asarray(c[3]), np.asarray(vaux))


def test_onehot_select_ignores_inf_in_unselected_slots():
    stacked = {"w": jnp.array([[1.0, 2.0], [jnp.inf, jnp.nan]])}
    out = onehot_select(stacked, jnp.int32(0))
    np.testing.assert_array_equal(np.asarray(out["w"]), [1.0, 2.0])


# ---------------------------------------------------------------------------
# round-block execution: K scanned rounds per host sync
# ---------------------------------------------------------------------------

def _block_pcfg(tiny_pcfg, **kw):
    """tiny_pcfg widened to 4 rounds with eval pushed past T so a block can
    actually span multiple rounds (eval rounds are host sync points)."""
    kw.setdefault("T", 4)
    kw.setdefault("eval_every", 10)
    return dataclasses.replace(tiny_pcfg, **kw)


def assert_rounds_identical(h_a, h_b):
    """Full-record bit-identity: every History key, including CommMeter
    totals, detections and train losses — stricter than
    assert_histories_equivalent(exact=True)."""
    assert len(h_a.rounds) == len(h_b.rounds)
    for ra, rb in zip(h_a.rounds, h_b.rounds):
        assert ra.keys() == rb.keys(), (set(ra) ^ set(rb))
        for k in ra:
            assert ra[k] == rb[k], (ra.get("round"), k, ra[k], rb[k])


@pytest.mark.parametrize("malicious,attack,tamper_check", [
    (set(), HONEST, False),
    ({1}, Attack(LABEL_FLIP), False),
    ({1}, Attack(LABEL_FLIP), True),
], ids=["honest", "label_flip", "label_flip+tamper_check"])
def test_block_history_bit_identical(tiny_task, tiny_pcfg, malicious, attack,
                                     tamper_check):
    """block=K must reproduce the per-round trajectory bit-for-bit: same
    selected-cluster sequence, same History floats, same CommMeter totals —
    the K-round scan changes only when the host observes theta, not what is
    computed."""
    data, module = tiny_task
    pcfg = _block_pcfg(tiny_pcfg, tamper_check=tamper_check)
    kw = dict(malicious=malicious, attack=attack, engine="batched",
              placement="vmap")
    h_1 = run_pigeon(module, data, pcfg, **kw, block=1)
    h_4 = run_pigeon(module, data, pcfg, **kw, block=4)
    assert_rounds_identical(h_1, h_4)


def test_block_sharded_bit_identical(tiny_task, tiny_pcfg):
    data, module = tiny_task
    pcfg = _block_pcfg(tiny_pcfg)
    kw = dict(malicious={1}, attack=Attack(LABEL_FLIP), engine="batched",
              placement="sharded")
    assert_rounds_identical(run_pigeon(module, data, pcfg, **kw, block=1),
                            run_pigeon(module, data, pcfg, **kw, block=4))


def test_block_selection_policy_bit_identical(tiny_task, tiny_pcfg):
    """Non-default selection policies ride inside the scanned cascade."""
    data, module = tiny_task
    pcfg = _block_pcfg(tiny_pcfg)
    kw = dict(malicious={1}, attack=Attack(LABEL_FLIP), engine="batched",
              placement="vmap", selection="loss_plus_distance")
    assert_rounds_identical(run_pigeon(module, data, pcfg, **kw, block=1),
                            run_pigeon(module, data, pcfg, **kw, block=4))


def test_block_eval_rounds_are_sync_points(tiny_task, tiny_pcfg):
    """Mid-stream eval rounds truncate blocks (plan_blocks) so test_acc is
    computed from exactly the per-round thetas."""
    data, module = tiny_task
    pcfg = _block_pcfg(tiny_pcfg, eval_every=2)
    kw = dict(engine="batched", placement="vmap")
    h_1 = run_pigeon(module, data, pcfg, **kw, block=1)
    h_4 = run_pigeon(module, data, pcfg, **kw, block=4)
    assert any("test_acc" in r for r in h_4.rounds[:-1])   # mid-stream eval
    assert_rounds_identical(h_1, h_4)


def test_block_splitfed_bit_identical(tiny_task, tiny_pcfg):
    data, module = tiny_task
    pcfg = _block_pcfg(tiny_pcfg)
    kw = dict(malicious={1}, attack=Attack(LABEL_FLIP), engine="batched",
              placement="vmap")
    assert_rounds_identical(run_splitfed(module, data, pcfg, **kw, block=1),
                            run_splitfed(module, data, pcfg, **kw, block=4))


def test_block_sweep_bit_identical(tiny_task, tiny_pcfg):
    data, module = tiny_task
    pcfg = _block_pcfg(tiny_pcfg)
    kw = dict(seeds=[0, 1], malicious={1}, attack=Attack(LABEL_FLIP),
              placement="vmap")
    hs_1 = run_pigeon_sweep(module, data, pcfg, **kw, block=1)
    hs_4 = run_pigeon_sweep(module, data, pcfg, **kw, block=4)
    for h_1, h_4 in zip(hs_1, hs_4):
        assert_rounds_identical(h_1, h_4)


def test_block_prefetch_compose(tiny_task, tiny_pcfg):
    """The feeder assembles whole blocks ahead; prefetch + block together
    still reproduce the synchronous per-round trajectory."""
    data, module = tiny_task
    pcfg = _block_pcfg(tiny_pcfg)
    kw = dict(malicious={1}, attack=Attack(LABEL_FLIP), engine="batched",
              placement="vmap")
    assert_rounds_identical(
        run_pigeon(module, data, pcfg, **kw, block=1),
        run_pigeon(module, data, pcfg, **kw, block=2, prefetch=2))


def test_check_block_validation(tiny_task, tiny_pcfg):
    """Up-front block validation mirrors _check_engine: impossible combos
    raise before any device work; host-sequenced modes force block=1 with a
    warning rather than silently diverging."""
    from repro.core.protocol import check_block
    data, module = tiny_task
    with pytest.raises(ValueError, match="block=0"):
        check_block(0)
    with pytest.raises(ValueError, match="engine"):
        check_block(2, "sequential")
    with pytest.raises(ValueError, match="checkpoint_every"):
        check_block(2, checkpoint_every=0)
    with pytest.raises(ValueError, match="block"):
        run_pigeon(module, data, tiny_pcfg, engine="sequential", block=2)
    for forced in (dict(plus=True), dict(has_param_tamper=True),
                   dict(force_host_selection=True)):
        with pytest.warns(UserWarning):
            assert check_block(4, **forced) == 1
    with pytest.warns(UserWarning):               # every round is a sync round
        assert check_block(4, eval_every=1) == 4  # kept: plan_blocks degrades
    assert check_block(1, plus=True) == 1         # block=1 never warns


def test_plan_blocks_tiles_and_respects_sync():
    from repro.data.pipeline import plan_blocks
    segs = plan_blocks(0, 10, 4, lambda t: t % 5 == 0 or t == 9)
    assert segs == [(0, 1), (1, 4), (5, 1), (6, 4)]
    assert sum(k for _, k in segs) == 10
    assert plan_blocks(3, 3, 4) == []
    assert plan_blocks(0, 5, 1) == [(t, 1) for t in range(5)]
    with pytest.raises(ValueError):
        plan_blocks(0, 5, 0)


def test_block_donation_no_retrace_and_donated_carry(tiny_task, tiny_pcfg):
    """Steady state of the block path: the second block re-uses the compiled
    scan program (one cached signature — no retrace) and the theta carry
    buffers of the previous block are donated (deleted after the call)."""
    import repro.core.engine as engine
    from repro.adversary import resolve_threat_model
    from repro.core.runner import protocol_accept_runner
    from repro.selection import resolve_policy

    data, module = tiny_task
    pcfg = _block_pcfg(tiny_pcfg)
    tm = resolve_threat_model(set(), HONEST, None)
    rng = np.random.default_rng(0)
    key = jax.random.PRNGKey(0)
    theta = module.init(jax.random.PRNGKey(1))
    x0, y0 = jnp.asarray(data.x0), jnp.asarray(data.y0)
    policy = resolve_policy("argmin")

    runner = protocol_accept_runner(module, pcfg.lr, "vmap", policy,
                                    pcfg.tamper_check, pcfg.tamper_tol,
                                    quant=pcfg.comm.quant)
    key, clusters_k, binputs = engine.assemble_block(rng, key, data, pcfg,
                                                     tm, 0, 2)
    theta1, _ = engine.pigeon_block_accept(module, theta, clusters_k, pcfg,
                                           tm, 0, binputs, x0, y0, policy)
    # the runner (and its compiled programs) is lru-shared across the suite,
    # so assert the steady-state property: a same-shape block adds NO new
    # compiled signature
    sigs = runner._jitted["accept_block"]._cache_size()
    key, clusters_k, binputs = engine.assemble_block(rng, key, data, pcfg,
                                                     tm, 2, 2)
    theta2, fetch = runner.accept_block(theta1, binputs, (x0, y0))
    jax.block_until_ready(fetch)
    assert runner._jitted["accept_block"]._cache_size() == sigs  # no retrace
    assert all(l.is_deleted() for l in jax.tree.leaves(theta1))  # donated


def test_accept_donation_no_retrace_and_donated_carry(tiny_task, tiny_pcfg):
    """Same steady-state guarantees for the existing per-round accept
    program: theta is donated round over round without retracing."""
    import repro.core.engine as engine
    from repro.adversary import resolve_threat_model
    from repro.core.protocol import CommMeter
    from repro.core.protocol import cut_width as protocol_cut_width
    from repro.core.runner import protocol_accept_runner
    from repro.selection import resolve_policy

    data, module = tiny_task
    tm = resolve_threat_model(set(), HONEST, None)
    rng = np.random.default_rng(0)
    key = jax.random.PRNGKey(0)
    theta = module.init(jax.random.PRNGKey(1))
    x0, y0 = jnp.asarray(data.x0), jnp.asarray(data.y0)
    policy = resolve_policy("argmin")
    meter = CommMeter()
    d_c = protocol_cut_width(module, theta[0], data.x0)

    runner = protocol_accept_runner(module, tiny_pcfg.lr, "vmap", policy,
                                    tiny_pcfg.tamper_check,
                                    tiny_pcfg.tamper_tol,
                                    quant=tiny_pcfg.comm.quant)
    thetas = [theta]
    for t in range(2):
        from repro.core.clustering import make_clusters
        clusters = make_clusters(rng, tiny_pcfg.M, tiny_pcfg.R)
        key, theta_next, _ = engine.pigeon_round_accept(
            module, thetas[-1], clusters, data, tiny_pcfg, tm, t, rng, key,
            meter, d_c, x0, y0, policy)
        thetas.append(theta_next)
        if t == 0:
            sigs = runner._jitted["accept"]._cache_size()
    jax.block_until_ready(thetas[-1])
    assert runner._jitted["accept"]._cache_size() == sigs      # no retrace
    # every superseded carry was donated back to the device allocator
    assert all(l.is_deleted() for l in jax.tree.leaves(thetas[1]))
