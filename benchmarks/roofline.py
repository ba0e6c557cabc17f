"""§Roofline: per-(arch x shape) three-term roofline from the compiled dry-run.

Methodology (see EXPERIMENTS.md §Roofline):

  * XLA's ``cost_analysis()`` counts loop *bodies once*, so a scanned
    126-layer model reports ~1 layer of FLOPs.  We therefore lower ANALYSIS
    variants with 1 and 2 repeats of the block unit (inner chunk loops
    widened to one trip: attn_q_chunk = seq, mlstm_chunk = seq, microbatch
    scan removed — the total tokens per step are unchanged, so the true
    per-step compute is identical) and extrapolate linearly:

        F_total = F(1) + (n_rep - 1 + n_tail/unit) * (F(2) - F(1))

    The same correction applies to bytes-accessed and collective bytes.
    Residual undercount: the sLSTM time-step scan (xlstm archs) — its
    recurrent cell is O(4 d hd) per token (< 2% of block FLOPs), noted
    rather than corrected.
  * The peak per-device memory (does-it-fit) comes from the REAL config's
    dry-run (dryrun_results.json), not the analysis variant.
  * Hardware: TPU v5e (197 TFLOP/s bf16, 819 GB/s HBM, 50 GB/s ICI).

Must run under the dry-run device flag; use:
    PYTHONPATH=src python -m benchmarks.roofline --pairs all
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

try:  # registry available when src/ is importable (the usual CLI setup)
    from repro.experiments import (ExperimentSpec, ScenarioSpec,
                                   build_experiment, register_experiment)
except ImportError:  # pragma: no cover - bare script usage
    register_experiment = None
    build_experiment = None


if register_experiment is not None:
    @register_experiment("roofline",
                         "per-(arch x shape) three-term roofline from the "
                         "compiled dry-run (subprocess: needs 512 XLA "
                         "host devices)")
    def build_spec(quick: bool = True) -> ExperimentSpec:
        """The accelerator sweep as spec data: the (arch x shape) grid,
        artifact paths and the XLA device requirement live in
        ``scenario.extras``, so the registry CLI can enumerate, override
        (``--set extras.pairs=...``) and dispatch it like any other
        experiment — it just runs in a subprocess with the dry-run device
        flag instead of through the trace runner."""
        return ExperimentSpec(
            name="roofline",
            scenario=ScenarioSpec(n_traces=0, extras={
                "external_runner": "benchmarks.roofline",
                "pairs": "tinyllama-1.1b:train_4k" if quick else "all",
                "rules": None,
                "tag": None,
                "dryrun_json": "dryrun_results.json",
                "out": "roofline_results.json",
                "xla_devices": 512,
            }),
            strategies=(),
            metrics=(),
            description="compiled-HLO roofline sweep (FLOPs / HBM / ICI "
                        "terms per arch x shape)",
        )


def spec_args(exp) -> tuple[list[str], dict[str, str]]:
    """Derive the subprocess argv tail + env for a spec-driven run.

    Shared by the registry CLI (``benchmarks.run --experiment roofline``)
    and ``--from-spec``; unit-testable without jax or the device flag.
    """
    extras = dict(exp.scenario.extras)
    args = ["--pairs", str(extras.get("pairs", "all")),
            "--dryrun-json", str(extras.get("dryrun_json",
                                            "dryrun_results.json")),
            "--out", str(extras.get("out", "roofline_results.json"))]
    if extras.get("rules"):
        args += ["--rules", str(extras["rules"])]
    if extras.get("tag"):
        args += ["--tag", str(extras["tag"])]
    for key, value in dict(extras.get("overrides", {})).items():
        args += ["--set", f"{key}={value}"]
    n_dev = int(extras.get("xla_devices", 512))
    # The sweep lowers for placeholder host devices: the child stays off
    # any accelerator (the parent, or another process, may hold it).
    env = {"XLA_FLAGS": f"--xla_force_host_platform_device_count={n_dev}",
           "JAX_PLATFORMS": "cpu"}
    return args, env


def _require_devices() -> None:
    # Honour a device-count flag already set by the caller (the registry
    # CLI's subprocess env, or --from-spec) — only default to 512 when
    # none is present, and import jax immediately to lock the flag.
    if "--xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count=512"
        import jax  # noqa: F401  (locks the flag; must be first init)


def apply_overrides(cfg, overrides: dict):
    import dataclasses as _dc
    return _dc.replace(cfg, **overrides) if overrides else cfg


def analysis_cfg(cfg, shape, n_units: int):
    """Analysis variant: n_units repeats, unrolled loops, real chunking.

    Chunk sizes stay at production values (they define the actual work for
    chunkwise mLSTM and the block schedule for attention); unrolling makes
    every trip visible to cost_analysis.  Attention q-chunks are widened to
    2048 to bound HLO size (same total FLOPs — attention chunking is
    work-preserving, unlike mLSTM chunking)."""
    return dataclasses.replace(
        cfg, n_layers=n_units * len(cfg.block_unit), microbatches=1,
        attn_q_chunk=2048, attn_kv_chunk=4096,
        scan_layers=False, unroll_inner=True)


def measure(cfg, shape, mesh, rules=None) -> dict:
    from repro.launch import hlo
    from repro.launch.steps import lower_step
    pair = lower_step(cfg, shape, mesh, compile_now=True, rules=rules)
    cost = pair.compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    stats = hlo.collective_bytes(pair.compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": float(stats.total),
        "n_coll": stats.n_ops,
    }


def corrected_pair(arch: str, shape_name: str, mesh, mesh_name: str,
                   fit_row: dict | None, overrides: dict | None = None,
                   rules=None) -> dict:
    from repro.configs import SHAPES, get
    from repro.launch import hlo
    from repro.launch.dryrun import model_flops

    cfg = apply_overrides(get(arch).for_shape(SHAPES[shape_name]),
                          overrides or {})
    shape = SHAPES[shape_name]
    unit = len(cfg.block_unit)
    n_rep = cfg.n_layers // unit
    n_tail = cfg.n_layers - n_rep * unit

    f1 = measure(analysis_cfg(cfg, shape, 1), shape, mesh, rules)
    if n_rep + n_tail / unit > 1:
        f2 = measure(analysis_cfg(cfg, shape, 2), shape, mesh, rules)
        mult = (n_rep - 1) + n_tail / unit
        tot = {k: f1[k] + mult * (f2[k] - f1[k])
               for k in ("flops", "bytes", "coll")}
        tot["n_coll"] = f1["n_coll"] + int(mult * (f2["n_coll"]
                                                   - f1["n_coll"]))
    else:
        tot = f1
    hw = hlo.V5E
    mf = model_flops(cfg, shape)
    n_dev = mesh.devices.size
    row = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "hlo_flops_per_dev": tot["flops"],
        "hlo_bytes_per_dev": tot["bytes"],
        "coll_bytes_per_dev": tot["coll"],
        "n_collectives": tot["n_coll"],
        "t_compute_s": tot["flops"] / hw.flops_bf16,
        "t_memory_s": tot["bytes"] / hw.hbm_bw,
        "t_collective_s": tot["coll"] / hw.ici_bw,
        "model_flops": mf,
        "useful_flops_ratio": mf / max(tot["flops"] * n_dev, 1.0),
        "bytes_per_device": (fit_row or {}).get("bytes_per_device"),
        "fits_hbm": (fit_row or {}).get("fits_hbm"),
    }
    terms = {"compute": row["t_compute_s"], "memory": row["t_memory_s"],
             "collective": row["t_collective_s"]}
    row["dominant"] = max(terms, key=terms.get)
    row["roofline_bound_s"] = max(terms.values())
    row["roofline_fraction"] = row["t_compute_s"] / max(
        row["roofline_bound_s"], 1e-12)
    return row


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--pairs", default=None,
                    help='"all" or comma list arch:shape (default: the '
                         'spec value with --from-spec, else "all")')
    ap.add_argument("--dryrun-json", default=None)
    ap.add_argument("--out", default=None)
    ap.add_argument("--append", action="store_true")
    ap.add_argument("--set", action="append", default=[],
                    help="cfg override key=value (python literal)")
    ap.add_argument("--rules", default=None,
                    choices=[None, "default", "seq_parallel", "decode"])
    ap.add_argument("--tag", default=None,
                    help="variant tag recorded with each row")
    ap.add_argument("--from-spec", default=None, metavar="NAME",
                    help="take pairs/artifact paths/overrides/device "
                         "count from the registered experiment spec "
                         "(e.g. 'roofline') as *defaults* — explicit "
                         "flags still win, --set entries append")
    ap.add_argument("--quick", action="store_true",
                    help="with --from-spec: the spec's quick-mode grid")
    args = ap.parse_args()

    # Spec extras are fallbacks for flags the user did not pass.
    extras: dict = {}
    if args.from_spec:
        if build_experiment is None:
            raise SystemExit("--from-spec needs the registry importable: "
                             "run with PYTHONPATH=src")
        exp = build_experiment(args.from_spec, quick=args.quick)
        extras = dict(exp.scenario.extras)
        args.set = [f"{k}={v}"
                    for k, v in dict(extras.get("overrides", {})).items()] \
            + args.set
        _, spec_env = spec_args(exp)
        os.environ.setdefault("XLA_FLAGS", spec_env["XLA_FLAGS"])
    if args.pairs is None:
        args.pairs = str(extras.get("pairs", "all"))
    if args.dryrun_json is None:
        args.dryrun_json = str(extras.get("dryrun_json",
                                          "dryrun_results.json"))
    if args.out is None:
        args.out = str(extras.get("out", "roofline_results.json"))
    if args.rules is None and extras.get("rules"):
        args.rules = str(extras["rules"])
    if args.tag is None and extras.get("tag"):
        args.tag = str(extras["tag"])

    _require_devices()
    import jax  # noqa: F401  (device flag locked above)
    from repro.configs import REGISTRY, SHAPES, get, skip_reason
    from repro.launch.mesh import make_production_mesh

    import ast
    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        try:
            overrides[k] = ast.literal_eval(v)
        except (ValueError, SyntaxError):
            overrides[k] = v
    rules = None
    if args.rules == "seq_parallel":
        from repro.parallel.sharding import SEQ_PARALLEL_RULES
        rules = SEQ_PARALLEL_RULES

    fits = {}
    if os.path.exists(args.dryrun_json):
        for r in json.load(open(args.dryrun_json)):
            if r.get("status") == "ok":
                fits[(r["arch"], r["shape"], r["mesh"])] = r

    mesh = make_production_mesh()
    mesh_name = "16x16"

    if args.pairs == "all":
        todo = [(c.name, s.name) for c in REGISTRY.values()
                for s in SHAPES.values() if not skip_reason(c, s)]
    else:
        todo = [tuple(p.split(":")) for p in args.pairs.split(",")]

    results = []
    if args.append and os.path.exists(args.out):
        results = json.load(open(args.out))
    done = {(r["arch"], r["shape"], r.get("tag")) for r in results}

    for arch, shape_name in todo:
        if (arch, shape_name, args.tag) in done:
            continue
        print(f"[roofline] {arch} x {shape_name} "
              f"{'(' + args.tag + ')' if args.tag else ''}...", flush=True)
        try:
            row = corrected_pair(arch, shape_name, mesh, mesh_name,
                                 fits.get((arch, shape_name, mesh_name)),
                                 overrides=overrides, rules=rules)
            if args.tag:
                row["tag"] = args.tag
                row["overrides"] = {k: str(v) for k, v in overrides.items()}
            print(f"  t_comp={row['t_compute_s']:.4f}s "
                  f"t_mem={row['t_memory_s']:.4f}s "
                  f"t_coll={row['t_collective_s']:.4f}s "
                  f"dominant={row['dominant']} "
                  f"useful={row['useful_flops_ratio']:.2f}", flush=True)
        except Exception as e:  # noqa: BLE001
            row = {"arch": arch, "shape": shape_name, "status": "error",
                   "error": f"{type(e).__name__}: {e}"}
            print(f"  ERROR {e}", flush=True)
        results.append(row)
        json.dump(results, open(args.out, "w"), indent=1)
    print(f"roofline -> {args.out}")


if __name__ == "__main__":
    main()
