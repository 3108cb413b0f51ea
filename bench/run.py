"""The benchmark's one command.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json on the chips of the machine it starts on
(see `bench.harness`) and prints, as the last line of standard output, one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``, each
compared number with its limit.  The compared numbers are also the last
lines of standard error.  Exits non-zero, with no result, when JAX finds no
accelerator or fewer chips than the cell asks for.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    try:
        from bench import harness
        import repro  # noqa: F401  (the program under test)
    except ImportError as e:
        print(f"bench: cannot import the benchmark or the program: {e}", file=sys.stderr)
        return 2
    return harness.run(a.workload, a.seed, a.seconds, bool(a.trace), t_process=T_PROCESS)


if __name__ == "__main__":
    sys.exit(main())
