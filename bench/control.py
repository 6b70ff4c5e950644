"""The comparison's control, on the chip at a cell's own size.

    python3 bench/control.py --workload alerts-1m.bulk --seed 7 --seconds 51

Runs the cell as ``bench/run.py`` does, then puts the reference held in
bfloat16, the next precision below the configuration's float32, in the
program's place and reads every number compared again. The result line's
``correct`` and ``checks`` are the control's, decided by the same rule as a
run's, and must come out false; the program's own verdict is printed on the
``program`` line before it. The benchmark's own runs never run this.
"""
import sys

import run

if __name__ == "__main__":
    sys.exit(run.main(control="bfloat16"))
