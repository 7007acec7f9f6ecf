"""A cell's description, gathered by name from data files.

``BENCHMARK.json`` names the cell's configuration and traffic; the rest
lies in files of their own, found by name:

  configs/<config>.json     the configuration as it is run
  traffic/<traffic>.json    the traffic mix's parameters
  workloads/<cell>.json     the lane that drives the cell, its strategy
                            document if the lane needs one, and the
                            limits of its correctness comparison
  peaks.json                the chip's peaks, keyed by ``device_kind``

Nothing here branches on a cell's or a configuration's name.
"""
from __future__ import annotations

import json
import pathlib
from dataclasses import dataclass

HERE = pathlib.Path(__file__).resolve().parent


class SpecError(RuntimeError):
    pass


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    workload: dict

    @property
    def lane(self) -> str:
        return self.workload["lane"]

    @property
    def limits(self) -> dict:
        return self.workload["limits"]


def read_json(path: pathlib.Path) -> dict:
    try:
        return json.loads(path.read_text())
    except OSError as e:
        raise SpecError(f"cannot read {path}: {e}") from e


def load_cell(bench_json: pathlib.Path, name: str,
              root: pathlib.Path = HERE) -> Cell:
    bench = read_json(bench_json)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in {bench_json}; have "
                        f"{sorted(cells)}")
    w = cells[name]
    return Cell(name=name, chips=int(w["chips"]),
                config_name=w["config"], traffic_name=w["traffic"],
                config=read_json(root / "configs" / f"{w['config']}.json"),
                traffic=read_json(root / "traffic" / f"{w['traffic']}.json"),
                workload=read_json(root / "workloads" / f"{name}.json"))


def peaks_for(kind: str, root: pathlib.Path = HERE) -> dict:
    table = read_json(root / "peaks.json")["devices"]
    if kind not in table:
        raise SpecError(f"device kind {kind!r} is not in peaks.json "
                        f"(have {sorted(table)}); add its published peaks")
    return table[kind]


def load_plugin(kind: str, name: str, root: pathlib.Path = HERE):
    """The module ``<kind>/<name>.py`` under the benchmark (a lane, a
    per-layer metric's reader or a FLOPs rule), loaded by file name."""
    import importlib.util
    path = root / kind / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no {kind} module {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def flops_per_token(cell: Cell, lane_info: dict) -> float:
    """Model FLOPs per token by the configuration's ``flops_rule``, a
    module ``flops/<rule>.py`` kept with the benchmark."""
    rule = load_plugin("flops", cell.config["flops_rule"])
    return float(rule.per_token(cell.config, cell.traffic, lane_info))
