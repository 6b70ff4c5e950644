"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload alerts-1m.bulk --seed 7 --seconds 30 \
        --trace 0

The cell, its configuration and traffic are read from ``BENCHMARK.json`` and
``bench/``. The last line of standard output is the result object; the last
lines of standard error are the numbers compared, each beside its limit.
A run that prints a result exits 0, correct or not. Without a TPU, or with
fewer chips than the cell asks for, it prints no result and exits 2.
"""
import time

T_START = time.perf_counter()  # set-up is timed from process start

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None, control=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    for path in (ROOT / "src", ROOT):
        if os.fspath(path) not in sys.path:
            sys.path.insert(0, os.fspath(path))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench import harness

    cell = harness.load_cell(args.workload)
    try:
        harness.run(cell, args.seed, args.seconds, bool(args.trace),
                    t_start=T_START, control=control)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
