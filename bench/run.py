"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload core125k.sim --seed 7 --seconds 30 \
        --trace 0

Builds the cell's network from the seed, compiles it for the target its
configuration names (`engine`, `hiaer` or `mesh`), deploys it in a
`SpikeServer` over the cell's chips, warms up the batch shapes its
traffic dispatches, offers that traffic for `--seconds`, checks every
answered window against the plain reference, and prints one JSON line
on standard output (diagnostics and the compared numbers go to standard
error). `--trace 1` reports the per-layer metrics from a profiler trace
of the window in place of the end-to-end ones. Exits non-zero, printing
no result, without a TPU (3) or for a cell whose chips cannot hold its
configuration's target (2).

The persistent compilation cache lives in `bench/.cache/jax` unless
`JAX_COMPILATION_CACHE_DIR` names another directory.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python3 bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    from benchkit.harness import CACHE, NoChip, run_cell
    from benchkit.program import Refused

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    except Refused as e:
        print(f"bench: cell {args.workload} refused: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
