#!/usr/bin/env python3
"""tools/find_knee_hybrid.py — ``tools/find_knee.py`` for a cell whose
traffic kind is ``open_loop_fixed`` (a ``serve_hybrid`` configuration): the
same sweep, arguments and result file, with the schedule handed to
``serve.run_load`` under the name it serves (``runners/serve_hybrid.py``'s
``generate``; ``find_knee`` imports the generator itself). Run by hand on
the chip when the cell's rate is chosen:

    python benchmark/tools/find_knee_hybrid.py \
        --workload smallthinker-mixed-queue --rates 1.5,2,2.5,3 --seconds 30
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.runners import serve_hybrid  # noqa: E402  (patches serve)
from benchmark.tools import find_knee  # noqa: E402

find_knee.generate = serve_hybrid.generate

if __name__ == "__main__":
    sys.exit(find_knee.main())
