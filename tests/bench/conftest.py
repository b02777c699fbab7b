"""Fixtures for the benchmark's CPU tests: the benchmark's modules on the
path, and a small-cell checkout (``smallcells.py``)."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "bench"))

from smallcells import make_checkout  # noqa: E402


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return make_checkout(tmp_path_factory.mktemp("checkout"))


@pytest.fixture(scope="module")
def program(checkout):
    import system

    system.import_program(checkout)
    import jax

    return jax.devices()[:1]
