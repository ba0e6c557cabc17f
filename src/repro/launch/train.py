"""Training launcher CLI.

Runs the fault-tolerant trainer on a reduced (CPU) or full (TPU) config:

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --steps 200 --seq 128 --batch 8 --faults --workdir ck

``--reduced`` (the default) trains the config's smoke-size variant, which
runs on a CPU; ``--no-reduced`` trains it at its published widths, which
needs an accelerator for all but the smallest configs.  Without
``--workdir`` the checkpoints go to a new temporary directory.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import tempfile

import numpy as np

from repro.configs import REGISTRY, get
from repro.configs.base import InputShape, PlatformConfig
from repro.configs.paper import SYNTHETIC
from repro.core.traces import Exponential, Weibull, make_event_trace
from repro.launch.compile_cache import enable_compile_cache
from repro.train import FaultTolerantTrainer


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="tinyllama-1.1b",
                    choices=sorted(REGISTRY))
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="train the smoke-size variant of the config "
                         "(--no-reduced: its published widths)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--workdir", default=None,
                    help="checkpoint directory (default: a new temporary "
                         "directory)")
    ap.add_argument("--faults", action="store_true",
                    help="inject faults from a synthetic trace")
    ap.add_argument("--fault-dist", default="exponential",
                    choices=["exponential", "weibull"])
    ap.add_argument("--mtbf", type=float, default=600.0,
                    help="platform MTBF in virtual seconds")
    ap.add_argument("--step-time", type=float, default=10.0,
                    help="virtual seconds per training step")
    ap.add_argument("--no-predictor", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def main() -> None:
    args = build_parser().parse_args()
    enable_compile_cache()
    workdir = args.workdir or tempfile.mkdtemp(prefix="repro_ckpt_")

    cfg = get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    shape = InputShape("cli", args.seq, args.batch, "train")
    plat = PlatformConfig(
        mu_ind=args.mtbf, c=3.0 * args.step_time, cp=args.step_time,
        d=args.step_time / 2, r=args.step_time,
        recall=SYNTHETIC.recall, precision=SYNTHETIC.precision)

    trace = None
    if args.faults:
        dist = Exponential(1.0) if args.fault_dist == "exponential" \
            else Weibull(0.7, 1.0)
        trace = make_event_trace(
            dist, args.mtbf, plat.recall, plat.precision,
            horizon=max(1e6, args.steps * args.step_time * 20),
            rng=np.random.default_rng(args.seed))

    trainer = FaultTolerantTrainer(
        cfg, shape, plat, workdir=workdir, step_time=args.step_time,
        trace=trace, use_predictor=not args.no_predictor, seed=args.seed)
    print(f"arch={cfg.name} period T*={trainer.scheduler.period:.1f}s "
          f"use_pred={trainer.scheduler.decision.use_predictions} "
          f"beta_lim={trainer.scheduler.decision.beta_lim:.1f}s")
    stats = trainer.run(args.steps)
    print(json.dumps(dataclasses.asdict(stats), indent=1))
    print(f"waste={stats.waste:.4f} "
          f"(analytic {trainer.scheduler.decision.expected_waste:.4f})")


if __name__ == "__main__":
    main()
