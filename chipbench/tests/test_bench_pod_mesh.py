"""The four-chip layout on four virtual CPU devices: one pod per device on
a pod mesh, the ring as a collective-permute, the codec under
``shard_map``, read by the ``ring_permute_ms`` reader.  No cell of the
benchmark has this layout yet (PERF.md, open questions); its traffic file
and reader are in place for the one that comes.  A subprocess, since the
device count is fixed when JAX starts."""
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

SCRIPT = r'''
import json, sys
import jax
from pathlib import Path
from chipbench import run
from chipbench.tests import cells
conf = cells.float32(cells.MAMBA)
traffic = dict(cells.GA, layout="pod_mesh", pods=4)
lim = json.loads((run.ROOT / "chipbench" / "limits" /
                  "mamba2-1.3b.ga-int8-every2.json").read_text())
ring = {"name": "ring_permute_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "transport",
        "moves": "step_ms_p90"}
bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
root = cells.make_root(Path(sys.argv[1]), [dict(
    name="c", config=conf, traffic_name="t4", traffic=traffic, chips=4)],
    limits={"c": lim}, per_layer=bench["per_layer"] + [ring])
res = run.run_cell("c", 2**31 + 9, 0.0, True, jax.devices()[:4], root=root)
print(json.dumps({k: res[k] for k in ("correct", "metrics", "checks")}))
'''


def test_pod_mesh_cell_on_four_devices(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    out = subprocess.run([sys.executable, "-c", SCRIPT, str(tmp_path)],
                         env=env, capture_output=True, text=True,
                         timeout=600, cwd=str(ROOT))
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"], res["checks"]
    assert res["metrics"]["compiles_in_window"]["value"] == 0
    assert res["metrics"]["ring_permute_ms"]["value"] > 0
