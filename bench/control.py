"""Run a cell with the control in the program's place: the plain paged
attention of ``bench/reference.py``, its query, keys and values rounded to
the precision next below the configuration's (``LOWER``), stands in for the
program's kernel, and the run's check must come out not correct.

    python3 bench/control.py --workload <cell> --seed <n> --seconds <s>

Prints the result line of ``bench/run.py``. The benchmark's own runs never
run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import functools  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:] = [ROOT, os.path.join(ROOT, "src")] + [
    p for p in sys.path if os.path.abspath(p or ".") != HERE]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


# the configuration's KV dtype -> the nearest precision below it
LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}


@contextlib.contextmanager
def control_attention(dtype: str):
    """Put the reference, its inputs rounded to the precision below
    ``dtype``, where the serving tier calls its paged-attention kernel."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.paged_attention import ops
    from bench.reference import paged_attention_jnp
    lowered = jax.jit(functools.partial(paged_attention_jnp,
                                        lower=jnp.dtype(LOWER[dtype])))
    original = ops.paged_attention

    def attention(q, kv_pages, block_tables, lengths, *, scale=None,
                  impl="kernel"):
        return lowered(q, kv_pages, block_tables, lengths)

    ops.paged_attention = attention
    try:
        yield
    finally:
        ops.paged_attention = original


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    args = parser.parse_args(argv)
    from bench import harness
    dtype = harness.load_cell(args.workload).config["dtype"]
    with control_attention(dtype):
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  False, T_START)
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
