"""Training launcher.

Runs the Pigeon-SL protocol (or a baseline) over any registered architecture
at smoke scale on CPU, or over the paper's CNNs:

  PYTHONPATH=src python -m repro.launch.train --task mnist --protocol pigeon+ \
      --attack label_flip --malicious 2 --rounds 10
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --smoke \
      --protocol pigeon --attack gradient --rounds 3
"""
from __future__ import annotations

import argparse
import json

from ..configs import get_smoke_config, list_archs
from ..core import (Attack, HONEST, ProtocolConfig, from_cnn, from_lm,
                    run_pigeon, run_splitfed, run_vanilla_sl)
from ..data import build_image_task, build_lm_task
from ..models import build_model
from ..telemetry import Stopwatch, Telemetry


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--task", default=None, choices=["mnist", "cifar10"])
    ap.add_argument("--arch", default=None, choices=list_archs())
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (required on CPU)")
    ap.add_argument("--protocol", default="pigeon+",
                    choices=["pigeon", "pigeon+", "vanilla", "sfl"])
    ap.add_argument("--attack", default="none",
                    choices=["none", "label_flip", "activation", "gradient",
                             "param_tamper"])
    ap.add_argument("--malicious", type=int, default=0,
                    help="number of malicious clients (first k ids)")
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--tolerance", type=int, default=1,
                    help="N, the malicious-client budget (R = N+1)")
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--local-steps", type=int, default=5, help="E")
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--lr", type=float, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a JSONL telemetry trace (spans + per-round "
                         "metrics + provenance) to PATH")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of round 1 into DIR")
    ap.add_argument("--engine", default=None,
                    choices=["sequential", "batched"],
                    help="round engine (default: batched when --block > 1, "
                         "else sequential)")
    ap.add_argument("--block", type=int, default=1,
                    help="round-block size: scan this many rounds on device "
                         "per host sync (pigeon/sfl batched engine only; "
                         "pigeon+ and param_tamper force 1)")
    ap.add_argument("--compile-cache", default=None, metavar="DIR",
                    help="JAX's persistent compilation cache directory "
                         "($JAX_COMPILATION_CACHE_DIR wins when set; default "
                         ".jax-compile-cache/ in the checkout)")
    args = ap.parse_args()

    from ..core import enable_compile_cache
    enable_compile_cache(args.compile_cache)

    engine = args.engine or ("batched" if args.block > 1 else "sequential")

    if args.task:
        data, cnn_cfg = build_image_task(args.task, m_clients=args.clients,
                                         d_m=300, d_o=150, n_test=1000,
                                         seed=args.seed)
        module = from_cnn(cnn_cfg)
        lr = args.lr or (0.05 if args.task == "mnist" else 0.02)
    else:
        arch = args.arch or "qwen3-8b"
        cfg = get_smoke_config(arch)
        model = build_model(cfg)
        module = from_lm(model)
        data = build_lm_task(vocab=cfg.vocab, seq_len=32,
                             m_clients=args.clients, d_m=64, d_o=32,
                             n_test=32, seed=args.seed)
        lr = args.lr or 5e-2

    pcfg = ProtocolConfig(M=args.clients, N=args.tolerance, T=args.rounds,
                          E=args.local_steps, B=args.batch, lr=lr,
                          seed=args.seed)
    attack = HONEST if args.attack == "none" else Attack(args.attack)
    malicious = set(range(args.malicious))
    telemetry = None
    if args.trace or args.profile_dir:
        telemetry = Telemetry(jsonl=args.trace, profile_dir=args.profile_dir)

    with Stopwatch() as sw:
        if args.protocol == "vanilla":
            hist = run_vanilla_sl(module, data, pcfg, malicious, attack,
                                  verbose=True, telemetry=telemetry)
        elif args.protocol == "sfl":
            hist = run_splitfed(module, data, pcfg, malicious, attack,
                                verbose=True, telemetry=telemetry,
                                engine=engine, block=args.block)
        else:
            hist = run_pigeon(module, data, pcfg, malicious, attack,
                              plus=args.protocol == "pigeon+", verbose=True,
                              telemetry=telemetry, engine=engine,
                              block=args.block)
    final = hist.rounds[-1].get("test_acc")
    print(f"done: {args.protocol} rounds={args.rounds} "
          f"final_test_acc={final} wall={sw.elapsed:.1f}s")
    if args.trace:
        print(f"telemetry trace: {args.trace}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(hist.rounds, f, indent=1, default=str)


if __name__ == "__main__":
    main()
