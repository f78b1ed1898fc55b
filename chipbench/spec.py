"""What a cell is made of, found by name.

A cell of `BENCHMARK.json` names a configuration and a traffic mix;
each lives in a file of its own under this directory, and each
per-layer metric in a reader of its own. Nothing here knows any cell,
configuration, mix or metric by name, so a later cell, configuration
or metric is added with files and `BENCHMARK.json` entries alone:

    chipbench/configs/<config>.json    sizes, serving settings, and the
                                       name of its plain reference
    chipbench/references/<ref>.py      that reference (`readings`)
    chipbench/traffic/<traffic>.json   parameters of the generator
    chipbench/limits/<workload>.json   the limits `correct` holds
    chipbench/metrics/<metric>.py      `read(ctx)` -> number or None
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import one file by path under a private module name."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One workload of BENCHMARK.json with its files read."""
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list          # metric entries this cell reports, trace 0
    per_layer: list           # metric entries this cell reports, trace 1

    def reference(self):
        return load_module(HERE / "references" / f"{self.config['reference']}.py",
                           f"chipbench_ref_{self.config['reference']}")


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    """One workload of `root`/BENCHMARK.json, its files read from
    `root`/chipbench."""
    here = root / HERE.name
    bench = load_json(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(by_name)}")
    w = by_name[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, workload) and m["moves"] in e2e_names]
    return Cell(
        name=workload, chips=int(w["chips"]),
        config=load_json(root / cfg_entry["file"]),
        traffic=load_json(here / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(here / "limits" / f"{workload}.json"),
        end_to_end=e2e, per_layer=per_layer)


def metric_reader(name: str):
    """The `read(ctx)` function of one per-layer metric."""
    mod = load_module(HERE / "metrics" / f"{name}.py",
                      "chipbench_metric_" + name.replace(".", "_")
                      .replace("-", "_"))
    return mod.read
