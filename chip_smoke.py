#!/usr/bin/env python3
"""On-chip smoke test: the Pigeon-SL round at the paper's Table II scale.

    python chip_smoke.py              # one TPU chip
    python chip_smoke.py --chips 4    # sharded placement on a four-chip host

With no option it runs, in this one process:

  kernels  the tamper-check and quantized-exchange Pallas kernels (compiled,
           not interpreted) against the ``kernels/ref.py`` oracles at the
           shared-set size D_o = 3000;
  cifar10  the CIFAR-10 split CNN (d_c = 256) at Table II scale through
           ``run_pigeon(engine="batched")`` with the defaults (vmap
           placement, argmin selection, tamper check on), then the same seed
           through ``engine="sequential"`` as the oracle; the round program's
           compiled text must hold the tamper-check kernel
           (``tpu_custom_call``);
  mnist    the same for the MNIST CNN at its Table II scale, shorter.

With ``--chips 4`` it runs only the MNIST Table II configuration (R = 4)
under ``placement="sharded"`` on a 4-device cluster mesh against the same
run under ``placement="vmap"``.

The lines before the last are diagnostics (compile seconds, steady round
seconds, oracle deltas), not benchmark metrics.  The last line is the JSON
result, printed only when every phase passed.  With no TPU the script exits
non-zero before any phase runs.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

# Oracle tolerance on per-cluster validation losses (relative, with a small
# absolute floor).  Both engines run the same f32 round on the same chip at
# the TPU's default matmul precision (one bf16 pass per f32 product, f32
# accumulation), where the CPU computes f32 products.  They differ only in
# program structure: the batched engine vmaps the R clusters and scans each
# client chain, the sequential engine jits each client turn, so the order of
# accumulation differs and the trajectories drift apart.  On a TPU v5e that
# drift was at most 3.5e-6 relative over the five rounds below; 1e-4 leaves
# room for it to grow with more rounds.  A bound this loose would not protect
# the selection: at these early rounds the best two cluster losses can be as
# close as 7e-6, so the selections are compared exactly and separately.
LOSS_RTOL = 1e-4
LOSS_ATOL = 1e-6

CIFAR_ROUNDS = 3
MNIST_ROUNDS = 2


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def table2(task: str, rounds: int, seed: int = 0):
    """(module, data, pcfg) of one paper CNN at Table II scale
    (``benchmarks/common.py``), synthetic data from ``seed``."""
    from benchmarks.common import cifar_scale, mnist_scale, pcfg_from
    from repro.core import from_cnn
    from repro.data import build_image_task

    scale = (cifar_scale if task == "cifar10" else mnist_scale)(True)
    data, cfg = build_image_task(task, m_clients=scale.m, d_m=scale.d_m,
                                 d_o=scale.d_o, n_test=scale.n_test, seed=seed)
    pcfg = dataclasses.replace(pcfg_from(scale, seed=seed), T=rounds)
    return from_cnn(cfg), data, pcfg


def timed_run(module, data, pcfg, **kw):
    """run_pigeon with round spans captured: (History, compile_s,
    first-round step seconds, steady step seconds per round)."""
    from repro.core import run_pigeon
    from repro.telemetry import MemorySink, Telemetry, jit_cache_stats

    sink = MemorySink()
    c0 = jit_cache_stats()["trace_compile_s"]
    hist = run_pigeon(module, data, pcfg, telemetry=Telemetry(sinks=(sink,)),
                      **kw)
    compile_s = jit_cache_stats()["trace_compile_s"] - c0
    steps = [e["dur_s"] for e in sink.of("span") if e["name"] == "round.step"]
    steady = sum(steps[1:]) / max(len(steps) - 1, 1)
    return hist, compile_s, steps[0] if steps else float("nan"), steady


def compare(name: str, ref_hist, hist) -> float:
    """Selections equal every round, validation losses finite and within
    tolerance; returns the largest relative loss delta."""
    import numpy as np

    check(len(ref_hist.rounds) == len(hist.rounds), f"{name}: round counts")
    worst = 0.0
    for r0, r1 in zip(ref_hist.rounds, hist.rounds):
        v0 = np.asarray(r0["val_losses"], np.float64)
        v1 = np.asarray(r1["val_losses"], np.float64)
        check(bool(np.isfinite(v0).all() and np.isfinite(v1).all()),
              f"{name}: non-finite validation losses {v0} {v1}")
        rel = float(np.max(np.abs(v1 - v0) / np.maximum(np.abs(v0), 1e-12)))
        worst = max(worst, rel)
        gap = np.sort(v0)[1] - np.sort(v0)[0] if len(v0) > 1 else float("inf")
        log(f"[{name}] round {r0['round']}: selected {r0['selected']} vs "
            f"{r1['selected']}, val_losses {np.round(v0, 6).tolist()} vs "
            f"{np.round(v1, 6).tolist()}, max rel delta {rel:.3e}, "
            f"best-two gap {gap:.3e}")
        check(r0["selected"] == r1["selected"],
              f"{name}: round {r0['round']} selected {r0['selected']} vs "
              f"{r1['selected']}")
        np.testing.assert_allclose(v1, v0, rtol=LOSS_RTOL, atol=LOSS_ATOL,
                                   err_msg=f"{name}: round {r0['round']}")
    return worst


def round_program_text(module, data, pcfg) -> str:
    """Compiled text of the batched engine's fused accept program (the
    lru-cached runner the driver used) at this configuration's shapes."""
    import jax
    import numpy as np

    from repro.adversary import resolve_threat_model
    from repro.core import HONEST
    from repro.core.clustering import make_clusters
    from repro.core.engine import assemble_round
    from repro.core.runner import protocol_accept_runner
    from repro.selection import resolve_policy

    runner = protocol_accept_runner(module, pcfg.lr, "vmap",
                                    resolve_policy("argmin"),
                                    pcfg.tamper_check, pcfg.tamper_tol,
                                    quant=pcfg.comm.quant)
    rng = np.random.default_rng(pcfg.seed)
    key = jax.random.PRNGKey(pcfg.seed)
    clusters = make_clusters(rng, pcfg.M, pcfg.R)
    _, payload = assemble_round(rng, key, data, clusters, pcfg,
                                resolve_threat_model(None, HONEST, None), 0)
    theta = module.init(key)
    val = (jax.numpy.asarray(data.x0), jax.numpy.asarray(data.y0))
    return runner.lower("accept", theta, payload, val).compile().as_text()


def phase_kernels() -> None:
    """Compiled kernels vs the pure-jnp oracles at D_o = 3000."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    k = jax.random.split(jax.random.PRNGKey(0), 3)
    for n, d in ((3000, 32), (3000, 256)):
        a = jax.random.normal(k[0], (5, n, d))
        b = a + 0.01 * jax.random.normal(k[1], (5, n, d))
        got = np.asarray(jax.jit(jax.vmap(ops.tamper_distance))(a, b))
        s = np.asarray(jax.vmap(ref.tamper_sums_reference)(a, b), np.float64)
        want = np.sqrt(s[:, 0]) / np.sqrt(s[:, 1])
        np.testing.assert_allclose(got, want, rtol=1e-4,
                                   err_msg=f"tamper_distance ({n}, {d})")
        log(f"[kernels] tamper_distance x5 ({n}, {d}): max rel err "
            f"{float(np.max(np.abs(got - want) / want)):.3e}")
    for n, d in ((64, 256), (3000, 256)):
        x = jax.random.normal(k[2], (n, d)) * 3.0 + 0.5
        deq_r, sc_r = (np.asarray(v) for v in
                       ref.quant_roundtrip_reference(x, "int8"))
        deq, sc = (np.asarray(v) for v in ops.quant_roundtrip(x, "int8"))
        deq_s, sc_s, st = (np.asarray(v) for v in
                           ops.quant_roundtrip_stats(x, "int8"))
        st_r = np.asarray(ref.message_stats_reference(jnp.asarray(deq_r)))
        for dq, s_ in ((deq, sc), (deq_s, sc_s)):
            np.testing.assert_allclose(s_, sc_r, rtol=1e-6,
                                       err_msg=f"int8 scales ({n}, {d})")
            # a division rounded differently may move a value across a
            # rounding boundary: at most one code (one scale step) apart
            check(bool(np.all(np.abs(dq - deq_r) <= sc_r[:, None] * 1.0001)),
                  f"int8 dequantized values ({n}, {d}) off by > one code")
        np.testing.assert_allclose(st, st_r, rtol=1e-4,
                                   err_msg=f"message stats ({n}, {d})")
        off = int(np.sum(np.abs(deq - deq_r) > 0.5 * sc_r[:, None]))
        log(f"[kernels] quant int8 ({n}, {d}): codes off by one "
            f"{off}/{deq.size}, stats {st.tolist()} vs {st_r.tolist()}")


def phase_task(task: str, rounds: int) -> None:
    """Batched engine at Table II scale vs the sequential oracle."""
    from repro.core import run_pigeon

    module, data, pcfg = table2(task, rounds)
    log(f"[{task}] M={pcfg.M} R={pcfg.R} E={pcfg.E} B={pcfg.B} "
        f"d_m={data.x.shape[1]} D_o={data.x0.shape[0]} T={pcfg.T}")
    hist, compile_s, first_s, steady_s = timed_run(module, data, pcfg,
                                                   engine="batched")
    log(f"[{task}] batched: compile_s {compile_s:.2f}, first round step "
        f"{first_s:.3f} s, steady round step {steady_s:.4f} s, test_acc "
        f"{[r.get('test_acc') for r in hist.rounds]}")
    t0 = time.perf_counter()
    oracle = run_pigeon(module, data, pcfg, engine="sequential")
    log(f"[{task}] sequential oracle: {time.perf_counter() - t0:.1f} s")
    worst = compare(task, oracle, hist)
    log(f"[{task}] oracle: selections equal, max rel loss delta {worst:.3e} "
        f"(tolerance rtol {LOSS_RTOL}, atol {LOSS_ATOL})")
    txt = round_program_text(module, data, pcfg)
    check("tpu_custom_call" in txt,
          f"{task}: accept program holds no tpu_custom_call")
    n_calls = txt.count('custom_call_target="tpu_custom_call"')
    log(f"[{task}] accept program: {n_calls} tpu_custom_call(s)")


def phase_sharded() -> None:
    """MNIST Table II (R = 4): sharded cluster axis on 4 chips vs vmap."""
    from repro.core.runner import cluster_mesh

    module, data, pcfg = table2("mnist", MNIST_ROUNDS)
    mesh = cluster_mesh(pcfg.R)
    log(f"[sharded] R={pcfg.R}, mesh {dict(mesh.shape)} over "
        f"{mesh.devices.size} devices")
    check(mesh.devices.size == 4, "cluster mesh does not span 4 devices")
    hist_s, c_s, _, st_s = timed_run(module, data, pcfg, engine="batched",
                                     placement="sharded")
    log(f"[sharded] sharded: compile_s {c_s:.2f}, steady round step "
        f"{st_s:.4f} s")
    hist_v, c_v, _, st_v = timed_run(module, data, pcfg, engine="batched",
                                     placement="vmap")
    log(f"[sharded] vmap: compile_s {c_v:.2f}, steady round step "
        f"{st_v:.4f} s")
    worst = compare("sharded", hist_v, hist_s)
    log(f"[sharded] selections equal, max rel loss delta {worst:.3e}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-vs-vmap phase on a "
                         "four-chip host")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 1
    log(f"device: {dev.platform} / {dev.device_kind} x {len(devices)}, "
        f"jax {jax.__version__}")

    from repro.core import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")

    phases = ([("sharded", phase_sharded)] if args.chips == 4 else
              [("kernels", phase_kernels),
               ("cifar10", lambda: phase_task("cifar10", CIFAR_ROUNDS)),
               ("mnist", lambda: phase_task("mnist", MNIST_ROUNDS))])
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn()
        except BaseException:
            log(f"phase {name}: FAILED after {time.perf_counter() - t0:.1f} s")
            raise
        log(f"phase {name}: ok in {time.perf_counter() - t0:.1f} s")

    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
