#!/usr/bin/env python3
"""tools/check_precision_latent.py — ``tools/check_precision_mixed.py`` for a
``serve_latent`` cell: the same arguments, variants and result file, with
the one call into the plain reference laid over as ``runners/
serve_latent.py`` lays it. Run by hand on the chip when the cell's two
limits are set:

    python benchmark/tools/check_precision_latent.py \
        --workload kanana2-longdoc-queue --short 24 --long 2 --new 256 \
        --variants '{"bf16": {}, "int8_weights": {"quant_bits": 8}}'
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.runners import serve_latent  # noqa: E402,F401 — the overlay
from benchmark.tools import check_precision_mixed  # noqa: E402

if __name__ == "__main__":
    sys.exit(check_precision_mixed.main())
