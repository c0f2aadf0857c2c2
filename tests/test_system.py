"""End-to-end system tests: the full training driver (control plane ->
elastic plan -> geo data -> sync strategies -> checkpoints) and the serving
driver, exercised through their CLIs."""
import json
import os

import jax
import numpy as np
import pytest


def test_end_to_end_training_loss_decreases(tmp_path):
    from repro.launch.train import main
    summary = main([
        "--preset", "tiny", "--pods", "2", "--steps", "30",
        "--batch", "8", "--seq", "64", "--sync", "asgd_ga", "--interval", "4",
        "--lr", "0.1", "--data-ratio", "2:1", "--log-every", "0",
        "--ckpt-dir", str(tmp_path / "ck"), "--ckpt-every", "30",
    ])
    # loss must move on the structured bigram stream
    assert summary["loss_last"] < summary["loss_first"]
    assert summary["wan_traffic_mb"] > 0
    assert os.path.exists(tmp_path / "ck" / "manifest.json")


def test_end_to_end_uneven_split_masks(tmp_path):
    from repro.launch.train import main
    s = main(["--preset", "tiny", "--pods", "2", "--steps", "6",
              "--batch", "6", "--seq", "32", "--sync", "sma",
              "--interval", "2", "--data-ratio", "3:1", "--log-every", "0"])
    assert np.isfinite(s["loss_last"])


def test_end_to_end_serving():
    from repro.launch.serve import main
    results = main(["--arch", "granite-8b", "--smoke", "--batch", "2",
                    "--prompt-len", "8", "--new-tokens", "4",
                    "--requests", "3"])
    assert len(results) == 3


def test_layers_flag_cuts_depth_and_on_finish_sees_the_run():
    """``--layers`` cuts depth only (the summary records it) and must be a
    multiple of the layer-pattern period; ``on_finish`` sees every loss."""
    from repro.launch.train import main
    seen = {}
    s = main(["--arch", "mamba2-1.3b", "--smoke", "--layers", "1",
              "--pods", "2", "--steps", "4", "--batch", "2", "--seq", "16",
              "--sync", "asgd_ga", "--interval", "2", "--compress-topk",
              "0.05", "--int8", "--error-feedback", "--log-every", "0"],
             on_finish=lambda trainer, state, losses: seen.update(
                 losses=list(losses), pods=trainer.cfg.n_pods))
    assert s["layers"] == 1
    assert seen["pods"] == 2 and len(seen["losses"]) == 4
    assert np.all(np.isfinite(seen["losses"]))
    with pytest.raises(SystemExit):      # gemma2's pattern period is 2
        main(["--arch", "gemma2-27b", "--smoke", "--layers", "3",
              "--steps", "1"])
