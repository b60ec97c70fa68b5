"""Where the harness finds what a name in ``BENCHMARK.json`` stands for.

Each kind of file sits in a directory of its own and is found by its name:
``configs/<config>.json``, ``traffic/<traffic>.json``,
``plugins/<plugin>.py``, ``references/<plugin>.py`` (a plug-in's plain
reference) and ``metrics/<metric>.py``. ``DIRS`` lists, for
each kind, the directories searched in order; a test puts a directory of
its own in front. ``BENCHMARK`` is the file that names the cells.
"""
from __future__ import annotations

import importlib.util
import re
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
BENCHMARK = ROOT / "BENCHMARK.json"
DIRS = {kind: [BENCH / kind]
        for kind in ("configs", "traffic", "plugins", "references",
                     "metrics")}

_MODULES: dict = {}


def find(kind: str, name: str, suffix: str) -> Path:
    """The first ``<dir>/<name><suffix>`` of ``DIRS[kind]`` that exists."""
    for d in DIRS[kind]:
        p = Path(d) / f"{name}{suffix}"
        if p.is_file():
            return p
    raise FileNotFoundError(f"no {kind} file {name}{suffix} in "
                            f"{[str(d) for d in DIRS[kind]]}")


def module(path: Path):
    """The Python module in the file at ``path``, loaded once a process."""
    path = Path(path).resolve()
    mod = _MODULES.get(path)
    if mod is None:
        name = "bench_file_" + re.sub(r"\W", "_", str(path))
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return mod


def load(kind: str, name: str):
    """The module of ``kind`` (``plugins``, ``references`` or ``metrics``)
    named ``name``."""
    return module(find(kind, name, ".py"))
