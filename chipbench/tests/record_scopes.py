"""Record the small CPU traces that ``test_bench_scopes.py`` reads: one
traced run of each smoke cell through ``run.run_cell``, kept gzipped as
``data/cpu_scopes_<ga|ma>.xplane.pb.gz`` (the trace holds the compiled HLO
of its programs):

- ``ga``: the ASGD-GA int8 codec with error feedback (``cells.GA``), one
  pod on each of two devices, so that the ring is a collective-permute of
  its own (stacked on one device the roll fuses into the decode);
- ``ma``: model averaging (``cells.MA``), both pods stacked on one device.

    XLA_FLAGS=--xla_force_host_platform_device_count=2 JAX_PLATFORMS=cpu \
        python -m chipbench.tests.record_scopes
"""
import gzip
import tempfile
from pathlib import Path

import jax

from chipbench import run
from chipbench.tests import cells

DATA = Path(__file__).parent / "data"


def record(name: str, traffic: dict) -> None:
    chips = traffic["pods"] if traffic["layout"] == "pod_mesh" else 1
    with tempfile.TemporaryDirectory() as tmp:
        root = cells.make_root(Path(tmp), [dict(
            name=name, config=cells.MAMBA, traffic_name=name,
            traffic=traffic, chips=chips)])
        run.run_cell(name, 2**31 + 7, 0.0, True, jax.devices()[:chips],
                     root=root)
    pb, = run.TRACE_DIR.glob("**/*.xplane.pb")
    with gzip.open(DATA / f"cpu_scopes_{name}.xplane.pb.gz", "wb") as f:
        f.write(pb.read_bytes())


if __name__ == "__main__":
    record("ga", dict(cells.GA, layout="pod_mesh"))
    record("ma", cells.MA)
