"""Run one cell of the chip benchmark once.

    python -m benchmarks.chip.run --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The last line of standard output is the result: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device`` and, traced,
``breakdown``; ``checks`` comes last, each number compared beside its
limit.  The same checks are the last lines of standard error.  Without a
TPU, or with fewer chips than the cell asks for, the run exits non-zero
and prints no result.

JAX's persistent compilation cache is kept at ``.jax_cache`` in the
checkout, so only the first run of a cell in a checkout compiles.
"""
import time

PROCESS_START = time.perf_counter()

import argparse                                   # noqa: E402
import json                                       # noqa: E402
import os                                         # noqa: E402
import sys                                        # noqa: E402
from pathlib import Path                          # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.makedirs(ROOT / ".jax_cache", exist_ok=True)
    sys.path.insert(0, str(ROOT))

    from benchmarks.chip import harness
    harness.use_compile_cache()
    cell = harness.load_cell(args.workload)
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         process_start=PROCESS_START)
    for name, c in result["checks"].items():
        harness.log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    harness.log(f"correct: {result['correct']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
