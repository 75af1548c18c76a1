#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, on the chip this process holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Makes the weights and the experts' planes from the seed, warms up every
shape of the cell's traffic, serves the traffic for ``--seconds``, checks
the served tokens against the float32 reference, and prints one JSON
line: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device`` and, traced, ``breakdown``; ``check`` last, each compared
number with its limit.  Exits non-zero with no line when JAX finds no TPU
or fewer chips than the cell asks for.

``--control`` puts the float8 control in the program's place in the
check: the tokens it puts first are judged under the same limit, so a
sound limit makes the run come out not correct (for setting the limit;
the benchmark's runs leave it off).
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args()
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"bench: the program is not at {src}", file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, src]
    from bench import harness

    harness.set_env()
    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), t_start=T_START,
                             control=args.control)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
