"""The model FLOP counts, each kept beside its reference, read as they
were when ``costs.py`` held them itself: bit for bit, at sequence length
2048, for the configuration files pinned here."""
import json

import pytest

from chipbench import costs, spec

PINNED = {"chipbench/configs/mamba2-1.3b.l4.json": 1276108800.0,
          "chipbench/configs/granite-8b.l2.v6144.json": 2868903936.0}


@pytest.mark.parametrize("path", sorted(PINNED))
def test_train_flops_per_token_is_pinned(path):
    config = json.loads((spec.ROOT / path).read_text())
    reference = spec.load_reference(spec.ROOT, config["reference"])
    assert costs.train_flops_per_token(config, 2048, reference) \
        == PINNED[path]
