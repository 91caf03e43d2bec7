#!/usr/bin/env python3
"""tools/check_precision_hybrid.py — ``tools/check_precision.py`` for a
``serve_hybrid`` cell: the same variants, prompts, engines and result file,
with the reference margins taken as ``runners/serve_hybrid.py`` takes them
(the cell's own reference module and its keywords; ``check_precision``
calls ``serve_moe.reference_margins`` with OLMoE's). Run by hand on the
chip when the cell's ``logit_tolerance`` is set:

    python benchmark/tools/check_precision_hybrid.py \
        --workload smallthinker-mixed-queue --requests 12 \
        --variants '{"bf16": {}, "int8_weights": {"quant_bits": 8}}'
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.runners import serve_hybrid, serve_moe  # noqa: E402
from benchmark.tools import check_precision  # noqa: E402

serve_moe.reference_margins = serve_hybrid.reference_margins

if __name__ == "__main__":
    sys.exit(check_precision.main())
