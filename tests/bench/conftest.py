import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from tiny_bench import write_bench  # noqa: E402


@pytest.fixture
def tiny_checkout(tmp_path):
    write_bench(tmp_path)
    return tmp_path
