"""The benchmark's tests: the repository root on the import path (for
``bench``), and a module-wide temporary copy of the benchmark with tiny
cells."""
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    from benchkit import make_root
    return make_root(tmp_path_factory.mktemp("bench"))
