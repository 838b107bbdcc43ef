"""``BENCHMARK.json`` and the files it names, resolved by name.

A cell (one entry of ``workloads``) joins a configuration, a traffic mix
and the metrics that the cell reports. Each piece lives in files of its
own under ``bench/``, so a later cell, configuration or metric is added
with new files and new entries, never by editing these.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import re
from types import ModuleType

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict  # configs/<config>.json
    traffic_name: str
    traffic: dict  # traffic/<traffic>.json
    end_to_end: list  # metric entries of BENCHMARK.json this cell reports
    per_layer: list
    root: pathlib.Path = ROOT

    @property
    def params(self) -> dict:
        """Instance parameters: the configuration's, then the traffic's
        overrides."""
        return {**self.config["params"], **self.traffic.get("instance", {})}

    def program_module(self) -> ModuleType:
        return load_module(self.root / "bench" / "configs" / f"{self.config_name}.py")

    def reference_module(self) -> ModuleType:
        return load_module(
            self.root / "bench" / "configs" / f"{self.config_name}_ref.py"
        )


def load_module(path: pathlib.Path) -> ModuleType:
    """Import one file of the benchmark by its path."""
    spec = importlib.util.spec_from_file_location(
        "_bench_" + re.sub(r"\W", "_", str(path)), path
    )
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell named ``workload``; ``KeyError`` if there is none."""
    bench = load_benchmark(root)
    entries = {w["name"]: w for w in bench["workloads"]}
    if workload not in entries:
        raise KeyError(
            f"no workload {workload!r} in BENCHMARK.json "
            f"(have {sorted(entries)})"
        )
    w = entries[workload]
    return Cell(
        name=w["name"],
        chips=int(w["chips"]),
        config_name=w["config"],
        config=json.loads(
            (root / "bench" / "configs" / f"{w['config']}.json").read_text()
        ),
        traffic_name=w["traffic"],
        traffic=json.loads(
            (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text()
        ),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, w["name"])],
        per_layer=[m for m in bench["per_layer"] if _applies(m, w["name"])],
        root=root,
    )


def metric_reader(name: str, root: pathlib.Path = ROOT):
    """The ``read(ctx)`` function of ``metrics/<name>.py``."""
    return load_module(root / "bench" / "metrics" / f"{name}.py").read
