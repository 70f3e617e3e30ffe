"""Readings that the limits of a cell's comparison are set from.

    python -m benchmarks.chip.readings --workload <cell> --seeds 1,2,... \\
        [--control-seeds a,b,c] [--faults half_agents,half_batch] [--out F]

In one process, for each seed: the cell's data and pretrained model, one
``run_scenario`` call of the compared rounds on the timed path, and the
plain reference; the numbers of the program against the reference are the
lower readings.  For the control seeds, the reference in three-pass
bfloat16 (the precision below the configuration's) against the fp32
reference, and for each planted fault (``benchmarks.chip.faults``) the
program with the fault against the reference.  One JSON line per reading
on standard output and, with ``--out``, in that file.  Needs the chip the
cell asks for; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.makedirs(ROOT / ".jax_cache", exist_ok=True)
    sys.path.insert(0, str(ROOT))

    from benchmarks.chip import faults, harness
    harness.use_compile_cache()
    cell = harness.load_cell(args.workload)
    harness.check_device(cell.chips)
    ints = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    planted = [f for f in args.faults.split(",") if f]
    out = open(args.out, "a") if args.out else None

    def emit(kind, seed, nums, **extra):
        line = json.dumps({"workload": cell.name, "kind": kind,
                           "seed": seed, **nums, **extra})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    for seed in ints:
        t0 = time.perf_counter()
        data, res = harness.prepare(cell, seed)
        base = harness.jax.device_get(data.params)
        ev = harness.Eval(cell.model.evaluate, data.x_test, data.y_test)

        def program():
            ev.reset(harness.COMPARED)
            harness.timed_call(cell, res, data.params, ev,
                               harness.COMPARED)
            return ev.captured

        prog = program()
        ref = harness.reference_rounds(cell, data, res)
        emit("program", seed, harness.numbers(cell, prog, ref, base,
                                              data.x_test, data.y_test),
             pre_acc=data.pre_acc, seconds=time.perf_counter() - t0)
        if seed not in control:
            continue
        ctrl = harness.reference_rounds(cell, data, res, mode="bf16x3")
        emit("control", seed, harness.numbers(cell, ctrl, ref, base,
                                              data.x_test, data.y_test))
        for name in planted:
            with faults.FAULTS[name]():
                bad = program()
            emit(name, seed, harness.numbers(cell, bad, ref, base,
                                             data.x_test, data.y_test))
    return 0


if __name__ == "__main__":
    sys.exit(main())
