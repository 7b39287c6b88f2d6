"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the TPU chips the
cell asks for; with no TPU, or too few, it exits non-zero and prints no
result. ``--trace 1`` reports the cell's per-layer metrics from a profiler
trace of the window instead of its end-to-end metrics.
"""
import time

T_START = time.perf_counter()   # set-up is timed from the process's start

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# import the benchmark as the package ``bench`` and the program from src/;
# the script's own directory leaves the path (bench/trace.py must not stand
# in for the standard library's trace module)
sys.path[:] = [ROOT, os.path.join(ROOT, "src")] + [
    p for p in sys.path if os.path.abspath(p or ".") != HERE]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    try:
        from bench import harness
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), T_START)
    except Exception as e:   # noqa: BLE001 -- no result line on any failure
        import traceback
        traceback.print_exc()
        print(f"bench: {type(e).__name__}: {e}", file=sys.stderr, flush=True)
        return 1
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
