"""The cells that the per-cell tests (rehearsal, faults, control, knee) run:
every cell of ``BENCHMARK.json`` and the toy cell of
``data/toy/BENCHMARK.json``, a configuration made of new files alone, so
that the tests hold for any plug-in. A test takes them as ``indirect``
parameters of the ``cell`` or ``cell_files`` fixture (``conftest.py``).
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import pytest

from bench import files

TOY = Path(__file__).resolve().parent / "data" / "toy"


@dataclasses.dataclass(frozen=True)
class Cell:
    """A cell of a benchmark file; ``first`` holds ``configs/``,
    ``plugins/`` and the other kinds' directories searched before the
    bench's own (None for ``BENCHMARK.json``'s cells)."""
    name: str
    benchmark: Path
    first: Path | None = None

    @property
    def id(self) -> str:
        return self.name if self.first is None else \
            f"{self.first.name}.{self.name}"


def _cells(benchmark: Path, first: Path | None = None) -> list:
    return [Cell(w["name"], benchmark, first)
            for w in json.loads(benchmark.read_text())["workloads"]]


def point(monkeypatch, cell: Cell, *, cpu: bool = False) -> None:
    """The harness finds ``cell``'s benchmark file and its files; with
    ``cpu``, ``stack.load_config`` gives each configuration with its
    ``"cpu"`` preset in force. Only these tests apply the preset."""
    from bench import stack

    if cell.first is not None:
        for kind, dirs in files.DIRS.items():
            monkeypatch.setitem(files.DIRS, kind, [cell.first / kind, *dirs])
    monkeypatch.setattr(files, "BENCHMARK", cell.benchmark)
    if cpu:
        load = stack.load_config
        monkeypatch.setattr(stack, "load_config",
                            lambda name: with_cpu_preset(load(name)))


def with_cpu_preset(cfg: dict) -> dict:
    """``cfg`` with the keys of its ``"cpu"`` block in place of its own:
    the sizes at which a CPU test run holds the configuration."""
    return {**cfg, **cfg.get("cpu", {})}


def plugin(name: str):
    """The plug-in module of the configuration of the cell called ``name``
    in the benchmark file that the harness finds now."""
    from bench import harness, stack

    w = next(w for w in harness.load_benchmark()["workloads"]
             if w["name"] == name)
    return stack.plugin(stack.load_config(w["config"]))


def plugin_of(cell: Cell):
    """The plug-in module of ``cell``'s configuration."""
    with pytest.MonkeyPatch.context() as mp:
        point(mp, cell)
        return plugin(cell.name)


TOY_CELLS = _cells(TOY / "BENCHMARK.json", TOY)
ALL = _cells(files.BENCHMARK) + TOY_CELLS
CELLS = [pytest.param(c, id=c.id) for c in ALL]
