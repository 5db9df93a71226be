"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine with the TPU chips the cell asks
for.  The last line of standard output is the result as one JSON object;
the last lines of standard error are the numbers compared for
``correct``, each beside its limit.  Without a TPU it exits non-zero and
prints no result.
"""
import time

T_START = time.monotonic()

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

from benchlib.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
