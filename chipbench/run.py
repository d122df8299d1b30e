"""Run one cell of the chip benchmark once and print its result line.

    python chipbench/run.py --workload sc2-save-raw --seed 7 --seconds 30 \
        --trace 0

Exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for. The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(and ``breakdown`` with ``--trace 1``), then ``checks``: each number that
decided ``correct`` beside its limit.
"""

import time

T_PROCESS = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_process=T_PROCESS))
