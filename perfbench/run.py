"""Run one benchmark cell once; see ``perfbench/harness.py``.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>
"""
import time

T_START = time.time()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

# the checkout root, not this directory, heads the import path
sys.path[0] = str(Path(__file__).resolve().parents[1])

from perfbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
