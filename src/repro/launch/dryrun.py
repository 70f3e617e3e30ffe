"""Multi-pod dry-run driver.

Lowers + compiles every (architecture × input shape) cell on the production
mesh (single-pod 16×16 = 256 chips; multi-pod 2×16×16 = 512 chips), prints
memory/cost analysis, parses collective bytes from the HLO, and persists one
JSON record per cell under ``results/dryrun/``.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-0.6b \
      --shape train_4k [--multi-pod] [--step h2fed_round] [--out results/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --all   # full matrix
"""
# The VERY FIRST lines — before ANY other import — so the 512 placeholder
# host devices exist before jax locks the device count on first init.
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402
from pathlib import Path  # noqa: E402

import jax           # noqa: E402

from repro.configs.registry import ARCH_IDS, get_config        # noqa: E402
from repro.launch import hlo_analysis                          # noqa: E402
from repro.launch import steps as steps_mod                    # noqa: E402
from repro.launch.mesh import make_production_mesh             # noqa: E402

# v5e hardware constants for the roofline terms (per chip)
PEAK_FLOPS = 197e12       # bf16 FLOP/s
HBM_BW = 819e9            # bytes/s
LINK_BW = 50e9            # bytes/s per ICI link


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             step_kind: str = "default", overrides: dict | None = None):
    """Lower + compile one cell; returns the record dict."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = len(jax.devices()) if multi_pod else 256
    cfg = get_config(arch)
    overrides = dict(overrides or {})
    # step-level (non-ArchConfig) knobs for the h2fed_round variants
    qc = bool(overrides.pop("quantize_cloud", False))
    lar = int(overrides.pop("lar", 4))
    if overrides:
        import dataclasses as _dc
        flat = {k: v for k, v in overrides.items() if "." not in k}
        nested: dict = {}
        for k, v in overrides.items():
            if "." in k:
                outer, inner = k.split(".", 1)
                nested.setdefault(outer, {})[inner] = v
        for outer, kv in nested.items():
            flat[outer] = _dc.replace(getattr(cfg, outer), **kv)
        cfg = cfg.replace(**flat)
    t0 = time.time()

    if step_kind == "h2fed_round":
        from repro.core.h2fed import H2FedParams
        from repro.launch.h2fed_round import round_input_specs
        spec = round_input_specs(cfg, shape_name, mesh,
                                 hp=H2FedParams(local_epochs=1, lar=lar),
                                 quantize_cloud=qc)
    else:
        spec = steps_mod.input_specs(cfg, shape_name, mesh)

    with mesh:
        jitted = jax.jit(spec["fn"], in_shardings=spec["in_shardings"])
        lowered = jitted.lower(*spec["args"])
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    # trip-count-aware per-device analysis (XLA counts scan bodies once)
    an = hlo_analysis.analyze(hlo)

    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "step": step_kind,
        "desc": spec["desc"],
        "n_chips": 512 if multi_pod else 256,
        "adapted_window": spec["cfg"].attn_window,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        },
        "xla_cost_raw": {"flops_per_device": float(cost.get("flops", 0.0)),
                         "bytes_per_device":
                             float(cost.get("bytes accessed", 0.0))},
        "cost": {"flops_per_device": an["flops"],
                 "hbm_bytes_per_device": an["bytes"]},
        "collectives_per_device_bytes": an["collectives"],
        "roofline": {
            # per-device work / per-chip rate == global / (chips × rate)
            "compute_s": an["flops"] / PEAK_FLOPS,
            "memory_s": an["bytes"] / HBM_BW,
            "collective_s": an["collective_bytes"] / LINK_BW,
        },
    }
    dom = max(("compute_s", "memory_s", "collective_s"),
              key=lambda k: rec["roofline"][k])
    rec["roofline"]["dominant"] = dom
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(steps_mod.SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--step", default="default",
                    choices=("default", "h2fed_round"))
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--override", action="append", default=[],
                    metavar="KEY=VALUE",
                    help="ArchConfig override for §Perf variants, e.g. "
                         "--override mlstm_chunk=128 (repeatable)")
    ap.add_argument("--tag", default="",
                    help="suffix for the result file (perf variants)")
    args = ap.parse_args()

    overrides = {}
    for ov in args.override:
        k, v = ov.split("=", 1)
        try:
            v = int(v)
        except ValueError:
            try:
                v = float(v)
            except ValueError:
                pass
        overrides[k] = v

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    cells = []
    if args.all:
        for arch in ARCH_IDS:
            for shape in steps_mod.SHAPES:
                for mp in (False, True):
                    cells.append((arch, shape, mp))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells.append((args.arch, args.shape, args.multi_pod))

    failures = 0
    for arch, shape, mp in cells:
        tag = f"{arch}__{shape}__{'mp' if mp else 'sp'}" \
              + ("" if args.step == "default" else f"__{args.step}") \
              + (f"__{args.tag}" if args.tag else "")
        path = out_dir / f"{tag}.json"
        if path.exists():
            print(f"[skip-cached] {tag}")
            continue
        if (arch, shape) in steps_mod.SKIPS:
            rec = {"arch": arch, "shape": shape,
                   "mesh": "2x16x16" if mp else "16x16",
                   "skipped": steps_mod.SKIPS[(arch, shape)]}
            path.write_text(json.dumps(rec, indent=1))
            print(f"[SKIP] {tag}: {rec['skipped']}")
            continue
        try:
            rec = run_cell(arch, shape, mp, args.step, overrides)
            if overrides:
                rec["overrides"] = overrides
            path.write_text(json.dumps(rec, indent=1))
            r = rec["roofline"]
            print(f"[ok] {tag}: compile={rec['compile_s']}s "
                  f"compute={r['compute_s']:.2e}s memory={r['memory_s']:.2e}s "
                  f"collective={r['collective_s']:.2e}s dom={r['dominant']} "
                  f"peakMB={(rec['memory']['peak_bytes'] or 0)/1e6:.0f}")
        except Exception as e:  # noqa: BLE001 — record and continue
            failures += 1
            (out_dir / f"{tag}.FAIL.txt").write_text(traceback.format_exc())
            print(f"[FAIL] {tag}: {type(e).__name__}: {str(e)[:300]}")
    if failures:
        raise SystemExit(f"{failures} dry-run cells failed")


if __name__ == "__main__":
    main()
