"""The benchmark's registry: ``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, traffic mix, cell or metric
lives in a file of its own, found by the name ``BENCHMARK.json`` gives it:

- ``configs[].file``                 the configuration (sizes, reference)
- ``chipbench/reference/<reference>.py`` the configuration's plain reference,
  ``init``, ``loss`` and ``flops_per_token`` (``chipbench/reference/``)
- ``chipbench/traffic/<traffic>.json``  the traffic mix (data only)
- ``chipbench/limits/<workload>.json``  the correctness limits of a cell
- ``chipbench/metrics/<metric>.py``     the metric's reader, ``read(ctx)``

so a new configuration, cell or metric is picked up by adding files and
entries alone.  A configuration whose reference module is missing, or
lacks one of ``REFERENCE_API``, fails when its cell is loaded, before any
device work.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
REFERENCE_API = ("init", "loss", "flops_per_token")


@dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    kind: str                        # "end_to_end" | "per_layer"
    reader: Callable


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    limits: Dict
    metrics: List[Metric]
    reference: ModuleType            # the configuration's plain reference

    def metrics_of(self, kind: str) -> List[Metric]:
        return [m for m in self.metrics if m.kind == kind]


def _json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def _load_module(path: Path, prefix: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        prefix + re.sub(r"\W", "_", path.stem), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(root: Path, name: str) -> Callable:
    path = root / "chipbench" / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"metric {name!r} has no reader at {path}")
    return _load_module(path, "chipbench_metric_").read


@functools.lru_cache(maxsize=None)
def reference_at(path: str) -> ModuleType:
    """The plain reference module in the file ``path``, loaded once per
    path; fails where it lacks one of ``REFERENCE_API``."""
    if not Path(path).is_file():
        raise FileNotFoundError(f"no reference module at {path}")
    mod = _load_module(Path(path), "chipbench_reference_")
    missing = [f for f in REFERENCE_API
               if not callable(getattr(mod, f, None))]
    if missing:
        raise AttributeError(f"reference module {path} lacks "
                             f"{', '.join(missing)}")
    return mod


def load_reference(root: Path, name: str) -> ModuleType:
    """The reference a configuration names: ``chipbench/reference/<name>.py``
    under ``root``."""
    return reference_at(str(Path(root) / "chipbench" / "reference" /
                            f"{name}.py"))


def load_cell(workload: str, root: Optional[Path] = None) -> Cell:
    """Resolve one ``workloads`` entry of ``BENCHMARK.json`` to its files."""
    root = Path(root or ROOT)
    bench = _json(root / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        known = [w["name"] for w in bench["workloads"]]
        raise KeyError(f"unknown workload {workload!r}; known: {known}")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = _json(root / conf["file"])
    reference = load_reference(root, config["reference"])
    traffic = _json(root / "chipbench" / "traffic" / f"{entry['traffic']}.json")
    limits_path = root / "chipbench" / "limits" / f"{workload}.json"
    limits = _json(limits_path) if limits_path.is_file() else {}
    metrics = []
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if "workloads" in m and workload not in m["workloads"]:
                continue
            metrics.append(Metric(name=m["name"], unit=m["unit"],
                                  better=m["better"], source=m["source"],
                                  kind=kind, reader=load_reader(root,
                                                                m["name"])))
    return Cell(name=workload, chips=int(entry["chips"]), config=config,
                traffic=traffic, limits=limits, metrics=metrics,
                reference=reference)


def load_peaks(device_kind: str, root: Optional[Path] = None) -> Dict:
    """Published peaks of one chip, keyed by JAX's ``device_kind``.  A
    device missing from the table is an error, never a default."""
    table = _json(Path(root or ROOT) / "chipbench" / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"chipbench/peaks.json (known: "
                       f"{sorted(table['devices'])})")
    return table["devices"][device_kind]
