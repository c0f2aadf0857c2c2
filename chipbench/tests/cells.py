"""Small cells for CPU tests: a scratch benchmark root holding the
benchmark's readers and peaks and configurations cut to smoke sizes."""
from __future__ import annotations

import json
import shutil
from pathlib import Path
from typing import Dict, List

from chipbench.spec import ROOT

MAMBA = {
    "name": "mamba2-smoke", "arch": "mamba2-1.3b", "reference": "mamba2",
    "overrides": {"n_layers": 2, "d_model": 128, "vocab_size": 256,
                  "vocab_multiple": 64,
                  "ssm": {"state_dim": 16, "head_dim": 16,
                          "chunk_size": 16}},
    "model": {"n_layers": 2, "d_model": 128, "vocab_size": 256,
              "padded_vocab": 256, "ssm.state_dim": 16, "ssm.head_dim": 16,
              "ssm.expand": 2, "ssm.n_groups": 1, "ssm.conv_width": 4,
              "ssm.chunk_size": 16, "tie_embeddings": True,
              "norm_eps": 1e-06, "param_dtype": "bfloat16",
              "compute_dtype": "bfloat16"},
    "optimizer": "sgd", "lr": 0.02}

GRANITE = {
    "name": "granite-smoke", "arch": "granite-8b", "reference": "gqa_swiglu",
    "overrides": {"n_layers": 2, "d_model": 128, "n_heads": 4,
                  "n_kv_heads": 2, "d_ff": 256, "vocab_size": 256,
                  "vocab_multiple": 64},
    "model": {"n_layers": 2, "d_model": 128, "n_heads": 4, "n_kv_heads": 2,
              "resolved_head_dim": 32, "d_ff": 256, "vocab_size": 256,
              "padded_vocab": 256, "rope_theta": 10000000.0,
              "tie_embeddings": False, "norm_eps": 1e-06, "pos_embed": "rope",
              "param_dtype": "bfloat16", "compute_dtype": "bfloat16"},
    "optimizer": "sgd", "lr": 0.02}

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
    shutil.copytree(ROOT / "chipbench" / "metrics",
                    root / "chipbench" / "metrics", dirs_exist_ok=True)
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
