"""``pytest bench/tests``: the benchmark's own tests, on the CPU."""
import os
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
os.environ.setdefault("JAX_PLATFORMS", "cpu")


@pytest.fixture
def no_compile_cache(monkeypatch):
    """Runs in tests keep no persistent compile cache in the checkout."""
    import repro.launch.compile_cache as cc
    monkeypatch.setattr(cc, "enable", lambda: "off")


@pytest.fixture
def cell_files(request, monkeypatch, no_compile_cache) -> str:
    """``indirect`` over ``cells.CELLS``: the harness pointed at the cell's
    files, its configuration as written; returns the cell's name."""
    from bench.tests import cells

    cells.point(monkeypatch, request.param)
    return request.param.name


@pytest.fixture
def cell(request, monkeypatch, no_compile_cache) -> str:
    """``indirect`` over ``cells.CELLS``: the harness pointed at the cell's
    files, with its configuration's ``"cpu"`` preset in force; returns the
    cell's name."""
    from bench.tests import cells

    cells.point(monkeypatch, request.param, cpu=True)
    return request.param.name
