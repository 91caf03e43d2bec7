import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def pytest_configure(config):
    config.addinivalue_line("markers", "rehearsal: a whole cell end to end "
                            "on the CPU with --rehearse (minutes)")


def pytest_collection_modifyitems(config, items):
    if "rehearsal" in (config.getoption("-m") or ""):
        return
    skip = pytest.mark.skip(reason="rehearsal: run with -m rehearsal")
    for item in items:
        if "rehearsal" in item.keywords:
            item.add_marker(skip)
