"""A new cell and a new metric are picked up from new files alone: a
traffic mix, a workload entry, a reader and its metric entry."""
import json

import jax

from chipbench import run, spec
from chipbench.tests import cells


def test_new_cell_and_metric_from_files_only(tmp_path):
    root = cells.make_root(tmp_path, [dict(
        name="mamba.ga", config=cells.MAMBA, traffic_name="ga",
        traffic=cells.GA)])
    assert [m.name for m in spec.load_cell("mamba.ga", root).metrics_of(
        "per_layer")] == [m["name"] for m in json.loads(
            (root / "BENCHMARK.json").read_text())["per_layer"]]

    # the additions: one traffic file, one reader, two registry entries
    traffic = dict(cells.MA, rows_per_pod=4)
    (root / "chipbench" / "traffic" / "ma-wide.json").write_text(
        json.dumps(traffic))
    (root / "chipbench" / "metrics" / "traced_tokens.py").write_text(
        "def read(ctx):\n    return ctx.traced.tokens\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "mamba.ma-wide",
                               "config": cells.MAMBA["name"],
                               "traffic": "ma-wide", "chips": 1,
                               "why": "t"})
    bench["per_layer"].append({"name": "traced_tokens", "unit": "tokens",
                               "better": "higher", "source": "device_trace",
                               "layer": "model step", "moves":
                               "tokens_per_s", "workloads":
                               ["mamba.ma-wide"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell("mamba.ma-wide", root)
    assert cell.traffic["rows_per_pod"] == 4
    assert "traced_tokens" in [m.name for m in cell.metrics]
    assert "traced_tokens" not in [
        m.name for m in spec.load_cell("mamba.ga", root).metrics]
    res = run.run_cell("mamba.ma-wide", 3, 0.0, True, jax.devices()[:1],
                       root=root)
    steps = cell.traffic["trace_steps"]
    assert res["metrics"]["traced_tokens"]["value"] == \
        steps * traffic["pods"] * 4 * traffic["seq"]
