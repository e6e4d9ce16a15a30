"""Where the harness finds a cell's parts: by name, in files of their own.

A cell of ``BENCHMARK.json`` names a configuration (``configs`` entry, whose
``file`` holds the deployment's sizes), a traffic mix
(``traffic/<mix>.json``, parameters read by the one generator in
`chipbench.loadgen`) and, through the metric entries, per-layer metrics
(``metrics/<metric>.py``, each a ``read(run)`` function).  Adding a cell, a
mix or a metric is adding files and entries: nothing here changes.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Callable, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class SpecError(Exception):
    """BENCHMARK.json or a file it names is missing or malformed."""


def load_benchmark(root: Path = ROOT) -> dict:
    path = Path(root) / "BENCHMARK.json"
    try:
        return json.loads(path.read_text())
    except FileNotFoundError as e:
        raise SpecError(f"no BENCHMARK.json at {path}") from e


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SpecError(f"no workload named {name!r} in BENCHMARK.json")


def load_config(bench: dict, name: str, root: Path = ROOT) -> dict:
    for entry in bench["configs"]:
        if entry["name"] == name:
            cfg = json.loads((Path(root) / entry["file"]).read_text())
            if cfg.get("name") != name:
                raise SpecError(f"{entry['file']} names {cfg.get('name')!r},"
                                f" not {name!r}")
            return cfg
    raise SpecError(f"no configuration named {name!r} in BENCHMARK.json")


def load_traffic(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    path = Path(bench_dir) / "traffic" / f"{name}.json"
    try:
        mix = json.loads(path.read_text())
    except FileNotFoundError as e:
        raise SpecError(f"no traffic mix file {path}") from e
    mix.setdefault("name", name)
    return mix


def load_reader(name: str, bench_dir: Path = BENCH_DIR) -> Callable:
    """The ``read(run)`` function of ``metrics/<name>.py``."""
    path = Path(bench_dir) / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no reader file {path} for metric {name!r}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def end_to_end_for(bench: dict, cell: str) -> List[dict]:
    """The end-to-end metrics this cell reports (a metric with a
    ``workloads`` list is reported only in those cells)."""
    return [m for m in bench["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]]


def per_layer_for(bench: dict, cell: str) -> List[dict]:
    """The per-layer metrics this cell reports: those that list it, or,
    without a list, every cell that reports the metric they move."""
    moves = {m["name"] for m in end_to_end_for(bench, cell)}
    out = []
    for m in bench["per_layer"]:
        listed: Optional[list] = m.get("workloads")
        if (cell in listed) if listed is not None else (m["moves"] in moves):
            out.append(m)
    return out
