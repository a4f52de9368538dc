"""Find a cell's files by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix and
lists the metrics; the files sit beside the harness:

    bench/configs/<config>.json     a deployment
    bench/traffic/<mix>.json        a traffic mix
    bench/limits/<cell>.json        the limits ``correct`` is judged by
    bench/metrics/<metric>.py       a metric, ``read(rec)``
    bench/reference/<rule>.py       a stage rule, ``stage_loads(cfg)``,
                                    where a configuration names one
                                    under ``"stage_rule"``

A name that ``BENCHMARK.json`` does not know, or whose file is missing, is
an error: nothing falls back to a default.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import re
import sys
from collections.abc import Callable

from bench.reference import deployment

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


class SpecError(ValueError):
    """A cell, configuration, mix, limit file or metric that cannot be
    found or read."""


def _named_file(root: pathlib.Path, folder: str, name: str,
                suffix: str) -> pathlib.Path:
    if not isinstance(name, str) or not NAME.fullmatch(name) or ".." in name:
        raise SpecError(f"{name!r} is not a valid name")
    path = root / "bench" / folder / f"{name}{suffix}"
    if not path.is_file():
        raise SpecError(f"no file {path.relative_to(root)} for {name!r}")
    return path


def _json(root, folder, name) -> dict:
    path = _named_file(root, folder, name, ".json")
    try:
        return json.loads(path.read_text())
    except json.JSONDecodeError as e:
        raise SpecError(f"{path.relative_to(root)}: {e}") from None


@dataclasses.dataclass
class Cell:
    """Everything one cell of the benchmark is made of."""
    name: str
    chips: int
    config: dict
    mix: dict
    limits: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    readers: dict                 # metric name -> read(rec)
    # the configuration's stage rule, cfg -> list[deployment.StageLoad]
    stage_loads: Callable = deployment.stage_loads


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _named_function(root: pathlib.Path, folder: str, name: str,
                    function: str):
    path = _named_file(root, folder, name, ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{folder}_" + re.sub(r"\W", "_", name), path)
    module = importlib.util.module_from_spec(spec)
    # registered as an import would: a dataclass in the module looks its
    # module up in sys.modules
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    if not callable(getattr(module, function, None)):
        raise SpecError(f"{path.relative_to(root)} has no {function}()")
    return getattr(module, function)


def load_reader(root: pathlib.Path, name: str):
    return _named_function(root, "metrics", name, "read")


def load_stage_rule(root: pathlib.Path, config: dict):
    """The stage rule ``config`` names, or the Table-1 rule."""
    if "stage_rule" not in config:
        return deployment.stage_loads
    return _named_function(root, "reference", config["stage_rule"],
                           "stage_loads")


def load_cell(root: pathlib.Path, name: str) -> Cell:
    try:
        bench = json.loads((root / "BENCHMARK.json").read_text())
    except FileNotFoundError:
        raise SpecError(f"no BENCHMARK.json in {root}") from None
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"unknown workload {name!r}; BENCHMARK.json has "
                        f"{', '.join(cells)}")
    w = cells[name]
    if w["config"] not in {c["name"] for c in bench["configs"]}:
        raise SpecError(f"{name}: configuration {w['config']!r} is not "
                        f"listed under configs")
    end_to_end = [m for m in bench["end_to_end"] if _applies(m, name)]
    per_layer = [m for m in bench["per_layer"] if _applies(m, name)]
    config = _json(root, "configs", w["config"])
    return Cell(
        name=name, chips=int(w["chips"]), config=config,
        mix=_json(root, "traffic", w["traffic"]),
        limits=_json(root, "limits", name),
        end_to_end=end_to_end, per_layer=per_layer,
        readers={m["name"]: load_reader(root, m["name"])
                 for m in end_to_end + per_layer},
        stage_loads=load_stage_rule(root, config))
