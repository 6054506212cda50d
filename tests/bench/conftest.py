"""Fixtures of the benchmark's tests. Puts the checkout root (for the
``bench`` package) and ``src`` (for the program) on the import path."""
from __future__ import annotations

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory) -> pathlib.Path:
    """A checkout holding the benchmark and one tiny cell."""
    from bench_fixtures import make_tiny_root
    return make_tiny_root(tmp_path_factory.mktemp("bench_checkout"))
