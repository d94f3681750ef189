import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(scope="module")
def tiny_bench(tmp_path_factory):
    from bench.tests import tiny
    return tiny.make(tmp_path_factory.mktemp("tiny"))
