"""Small cells for CPU tests: a scratch benchmark root holding the
benchmark's readers, references and peaks, and configurations cut to smoke
sizes (``smoke/<config>.json``, one for each of ``BENCHMARK.json``'s
configurations)."""
from __future__ import annotations

import json
import shutil
from pathlib import Path
from typing import Dict, List

from chipbench.spec import ROOT

SMOKE = Path(__file__).parent / "smoke"


def smoke(config: str) -> Dict:
    """The smoke-size stand-in of the benchmark's configuration ``config``:
    ``smoke/<config>.json``, the same model family and reference at widths
    a CPU test run holds."""
    return json.loads((SMOKE / f"{config}.json").read_text())


MAMBA = smoke("mamba2-1.3b.l4")
GRANITE = smoke("granite-8b.l2.v6144")

GA = {"layout": "stacked", "pods": 2, "rows_per_pod": 2, "seq": 32,
      "sync": {"strategy": "asgd_ga", "interval": 2, "compress_topk": 0.02,
               "quantize_int8": True, "value_dtype": "int8",
               "error_feedback": True, "codec_block": 512,
               "overlap_chunks": 1},
      "pool": 8, "trace_steps": 4}

MA = {"layout": "stacked", "pods": 2, "rows_per_pod": 2, "seq": 32,
      "sync": {"strategy": "ama", "interval": 4}, "pool": 8,
      "trace_steps": 4}


def float32(config: Dict) -> Dict:
    """The same configuration with parameters and compute in float32."""
    c = json.loads(json.dumps(config))
    c["name"] += "-f32"
    c["overrides"].update(param_dtype="float32", compute_dtype="float32")
    c["model"].update(param_dtype="float32", compute_dtype="float32")
    return c


def make_root(tmp: Path, cells: List[Dict], limits: Dict = None,
              per_layer=None) -> Path:
    """A benchmark root under ``tmp``.  ``cells`` entries: ``name``,
    ``config`` (dict), ``traffic_name``, ``traffic`` (dict), ``chips``."""
    root = Path(tmp)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (root / "chipbench").mkdir(parents=True, exist_ok=True)
    for d in ("metrics", "reference"):
        shutil.copytree(ROOT / "chipbench" / d, root / "chipbench" / d,
                        dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("__pycache__"))
    peaks = json.loads((ROOT / "chipbench" / "peaks.json").read_text())
    # a nominal entry so that the CPU backend can stand in for a chip
    peaks["devices"]["cpu"] = dict(peaks["devices"]["TPU v5 lite"])
    (root / "chipbench" / "peaks.json").write_text(json.dumps(peaks))
    for d in ("configs", "traffic", "limits"):
        (root / "chipbench" / d).mkdir(exist_ok=True)
    bench["configs"], bench["workloads"] = [], []
    for c in cells:
        conf = c["config"]
        path = f"chipbench/configs/{conf['name']}.json"
        (root / path).write_text(json.dumps(conf))
        if conf["name"] not in [x["name"] for x in bench["configs"]]:
            bench["configs"].append({"name": conf["name"], "source": "test",
                                     "file": path, "reduced": [],
                                     "why": "test"})
        (root / "chipbench" / "traffic" /
         f"{c['traffic_name']}.json").write_text(json.dumps(c["traffic"]))
        bench["workloads"].append({"name": c["name"],
                                   "config": conf["name"],
                                   "traffic": c["traffic_name"],
                                   "chips": c.get("chips", 1), "why": "t"})
        lim = (limits or {}).get(c["name"])
        if lim is not None:
            (root / "chipbench" / "limits" /
             f"{c['name']}.json").write_text(json.dumps(lim))
    if per_layer is not None:
        bench["per_layer"] = per_layer
    for m in bench["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
