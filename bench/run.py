"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload W --seed N --seconds S --trace 0|1

Run from the root of a checkout on a machine that holds the chips the
cell asks for.  Exits non-zero, printing no result, when JAX finds no TPU
or fewer chips than the cell needs, or when the run fails.  The last line
of standard output is the result object described in ``bench/harness.py``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# the persistent compilation cache lives in the checkout, at a fixed path
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    from bench import harness

    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), t_start=T_START)
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except SystemExit:
        raise
    except BaseException as e:          # no result line on any failure
        import traceback
        traceback.print_exc()
        print(f"bench/run.py failed: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # the program's background compile thread can crash interpreter
    # teardown; every result is already printed
    os._exit(code)
