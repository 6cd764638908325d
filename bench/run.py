"""The benchmark's command: one run of one cell on the chip.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> \\
      --trace <0|1>

Runs from the root of a checkout that holds ``BENCHMARK.json``, ``bench/``
and the program under ``src/``.  One process holds the chip; the load
generator is a child process that never imports JAX.  With ``--trace 0``
the result carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics from a profiler trace of the window.

Progress goes to standard error, ending with each number the correctness
check compared and its limit.  The last line of standard output is the
result: {"correct", "attempted", "failed", "metrics", "device",
["breakdown"], "checks"}.  Without a TPU, or with fewer chips than the
cell asks for, the command exits non-zero and prints no result.

JAX's persistent compilation cache lives in ``.jax_cache`` at the root of
the checkout and keeps every executable, so only a checkout's first run
compiles.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    sys.path[:0] = [os.path.join(ROOT, "src"), os.path.dirname(__file__)]
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    import harness

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    try:
        result, checks, _ = harness.run_cell(
            ROOT, args.workload, args.seed, args.seconds, bool(args.trace),
            T_START, log=log)
    except harness.HarnessError as e:
        log(f"bench: {e}")
        return 2
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
