#!/usr/bin/env python3
"""tools/find_knee_mixed.py — ``tools/find_knee.py`` for a ``serve_mixed``
cell (traffic kind ``open_loop_fixed``): the same sweep, arguments and
result file, with the schedule handed to ``serve.run_load`` under the name
it serves, as ``tools/find_knee_hybrid.py`` does. Run by hand on the chip
when the cell's rate is chosen:

    python benchmark/tools/find_knee_mixed.py \
        --workload lfm2-mixed-queue --rates 2,3,4,5 --seconds 30
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.runners import serve_hybrid  # noqa: E402
from benchmark.tools import find_knee  # noqa: E402

find_knee.generate = serve_hybrid.generate

if __name__ == "__main__":
    sys.exit(find_knee.main())
