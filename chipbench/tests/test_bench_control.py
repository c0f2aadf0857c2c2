"""The control, the float32 reference computed with float8 weight
products, fails each cell's comparison: at a size a test run holds, on the
CPU, against the cell's own limits."""
import json

import pytest

from chipbench import check, data, run
from chipbench.reference import train as reference
from chipbench.spec import ROOT
from chipbench.tests import cells

CELLS = {"mamba2-1.3b.ga-int8-every2": ("mamba2", cells.MAMBA, cells.GA),
         "granite-8b.ma-every4": ("gqa_swiglu", cells.GRANITE, cells.MA)}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_control_fails(cell):
    arch, conf, traffic = CELLS[cell]
    limits = json.loads((ROOT / "chipbench" / "limits" /
                         f"{cell}.json").read_text())
    conf = cells.float32(conf)
    n = run.check_steps(traffic["sync"])
    seed = 2**31 + 21
    pool = data.batch_pool(seed, n, traffic["pods"], traffic["rows_per_pod"],
                           traffic["seq"], conf["model"]["vocab_size"])
    args = (conf["model"], arch, traffic["sync"], conf["lr"],
            run.key_for(seed), pool)
    numbers = check.compare(reference.train(*args, precision="fp8"),
                            reference.train(*args))
    ok, checks = check.judge(numbers, limits)
    assert not ok, checks
