"""Run one benchmark cell on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything the cell needs is found by name under ``bench/`` (see
``bench/harness.py``).  The run exits non-zero, and prints no result,
when JAX finds no TPU or fewer chips than the cell asks for.
"""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
