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
