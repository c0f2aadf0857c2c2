"""A new cell, metric or configuration is picked up from new files alone:
a traffic mix, a workload entry, a reader and its metric entry; a
reference module and a configuration that names it."""
import json
import re
import shutil

import jax
import pytest

from chipbench import run, spec
from chipbench.spec import ROOT
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


def test_new_configuration_from_files_only(tmp_path):
    """A configuration whose reference the benchmark does not have joins
    by new files alone: its reference module, its configuration, a limits
    file, a reader of a scope nested under ``train_forward`` (the layer
    loop), and registry entries.  The cell comes out correct, and the
    model FLOPs its reference counts reach ``train_mfu`` and ``step_mfu``."""
    conf = cells.float32(cells.GRANITE)
    root = cells.make_root(tmp_path, [dict(
        name="granite.ma", config=conf, traffic_name="ma",
        traffic=cells.MA)])

    # the additions: a reference under a new name, its configuration, the
    # cell's limits, one reader, three registry entries
    bench_dir = root / "chipbench"
    shutil.copy(bench_dir / "reference" / "gqa_swiglu.py",
                bench_dir / "reference" / "gqa_copy.py")
    new = dict(conf, name="gqa-copy", reference="gqa_copy")
    (bench_dir / "configs" / "gqa-copy.json").write_text(json.dumps(new))
    shutil.copy(ROOT / "chipbench" / "limits" / "granite-8b.ma-every4.json",
                bench_dir / "limits" / "gqa-copy.ma.json")
    (bench_dir / "metrics" / "layer_loop_fwd_ms.py").write_text(
        "from chipbench import scopes\n\n\n"
        "def read(ctx):\n"
        "    return scopes.named_ms(scopes.of(ctx), 'while', "
        "ctx.traced.steps, backward=False)\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "gqa-copy", "source": "test",
                             "file": "chipbench/configs/gqa-copy.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "gqa-copy.ma", "config": "gqa-copy",
                               "traffic": "ma", "chips": 1, "why": "t"})
    bench["per_layer"].append({"name": "layer_loop_fwd_ms", "unit": "ms",
                               "better": "lower", "source": "device_trace",
                               "layer": "model step", "moves":
                               "tokens_per_s", "workloads": ["gqa-copy.ma"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell("gqa-copy.ma", root)
    assert cell.reference.__file__ == str(bench_dir / "reference" /
                                          "gqa_copy.py")
    res = run.run_cell("gqa-copy.ma", 2**31 + 9, 0.0, True,
                       jax.devices()[:1], root=root)
    assert res["correct"], res["checks"]
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert got["train_mfu"] > got["step_mfu"] > 0
    assert 0 < got["layer_loop_fwd_ms"] <= got["train_fwd_ms"]


@pytest.mark.parametrize("fault", ["missing", "no_flops_per_token"])
def test_configuration_without_a_sound_reference_fails_on_load(tmp_path,
                                                               fault):
    """A configuration whose reference module is missing, or lacks one of
    the functions a reference provides, fails when its cell is loaded,
    naming the file, before the program is built."""
    root = cells.make_root(tmp_path, [dict(
        name="broken.ma", config=dict(cells.GRANITE, reference="broken"),
        traffic_name="ma", traffic=cells.MA)])
    path = root / "chipbench" / "reference" / "broken.py"
    if fault == "no_flops_per_token":
        path.write_text((path.parent / "gqa_swiglu.py").read_text().replace(
            "def flops_per_token(", "def _flops_per_token("))
    error = FileNotFoundError if fault == "missing" else AttributeError
    with pytest.raises(error, match=re.escape(str(path))):
        spec.load_cell("broken.ma", root)
    built = []
    with pytest.raises(error, match=re.escape(str(path))):
        run.run_cell("broken.ma", 1, 0.0, False, jax.devices()[:1],
                     root=root, fault=built.append)
    assert not built
