"""Readings the correctness limits are set from: for each seed, one run
of a cell exactly as ``bench/run.py`` makes it, whose check also
computes the control (the reference in float8 in the program's place)
on the same prompts and served tokens.  All seeds run in one process,
one after another, so the set-up's compiles are paid once.

  python3 bench/calibrate.py --workload <name> --seconds <s> \\
      --seeds 1 2 3 ... [--gaps <file>]

Prints one JSON line per seed: {"seed", "correct", "tokens_compared",
"logit_gap_max", "logit_gap_mean", "control_logit_gap_max",
"control_logit_gap_mean", "metrics"}; with ``--gaps``, also appends
each seed's per-token gaps, the program's and the control's, to that
file as a JSON line.  The benchmark's own runs never compute the
control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--gaps", help="file to append per-token gaps to")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [os.path.join(ROOT, "src"), os.path.dirname(__file__)]
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    import harness
    for seed in args.seeds:
        result, _, readings = harness.run_cell(
            ROOT, args.workload, seed, args.seconds, False, time.monotonic(),
            control=True, log=lambda m: print(m, file=sys.stderr, flush=True))
        gaps = readings.pop("gaps")
        print(json.dumps({"seed": seed, "correct": result["correct"],
                          **readings, "metrics": result["metrics"]}),
              flush=True)
        if args.gaps:
            with open(args.gaps, "a") as f:
                f.write(json.dumps({"seed": seed, **{
                    k: [round(float(x), 5) for x in v]
                    for k, v in gaps.items()}}) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
