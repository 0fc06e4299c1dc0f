"""`python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>`

One run of one cell of BENCHMARK.json on the machine it is started on:
one process, no fallback to the CPU. The last line of standard output is
the result; without a chip, or without the program, there is none and
the exit code is not 0.
"""

from __future__ import annotations

import argparse
import pathlib
import sys
import time

T_START = time.monotonic()
ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default=None,
                    help="comma-separated controls of the cell's driver, "
                         "each judged in the program's place once the run "
                         "itself is judged (never set by the driver)")
    args = ap.parse_args(argv)
    import onix  # noqa: F401  the system under test: without it, no run
    from benchmark import harness
    try:
        line = harness.run_cell(args.workload, args.seed, args.seconds,
                                bool(args.trace), control=args.control,
                                t_start=T_START)
    except harness.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    harness.print_result(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
