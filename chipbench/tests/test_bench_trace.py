"""The trace reduction on a small trace recorded on the CPU backend: four
train steps and two sync rounds inside ``bench.*`` spans."""
from pathlib import Path
from types import SimpleNamespace

import pytest
from jax.profiler import ProfileData

from chipbench import costs, spec, trace
from chipbench.tests import cells

DATA = Path(__file__).parent / "data" / "cpu_trace.xplane.pb"


@pytest.fixture(scope="module")
def tr():
    return trace.from_profile(ProfileData.from_file(str(DATA)))


def test_programs_and_spans(tr):
    assert tr.devices == [0]
    assert len(trace.module_runs(tr, "jit__train_step_impl")[0]) == 4
    assert len(trace.module_runs(tr, "jit__sync_step_impl")[0]) == 2
    names = [s.name for s in tr.spans]
    assert names.count("bench.train_step") == 4
    assert names.count("bench.maybe_sync") == 2
    assert names[-1] == "bench.block"
    assert all(op.module for op in tr.ops)


def test_busy_and_gaps_cover_the_window(tr):
    lo, hi = tr.window()
    busy = trace.busy_ns(tr, 0, lo, hi)
    gaps = trace.idle_gaps(tr, 0, lo, hi)
    assert 0 < busy < hi - lo
    assert abs(busy + sum(t - s for s, t in gaps) - (hi - lo)) < 1.0
    # the host sleeps 2 ms in every batch hand-off: the device idles there
    labels = {trace.span_at(tr, (s + t) / 2) for s, t in gaps
              if t - s > 1.5e6}
    assert "bench.batch" in labels


def test_readers_on_the_trace(tr):
    lo, hi = tr.window()
    ctx = SimpleNamespace(trace=tr, traced=SimpleNamespace(
        lo=lo, hi=hi, rounds=2, steps=4, seconds=(hi - lo) / 1e9))
    idle = spec.load_reader(spec.ROOT, "device_idle_share")(ctx)
    assert 0 < idle < 100
    step = spec.load_reader(spec.ROOT, "train_step_ms")(ctx)
    runs = trace.module_runs(tr, "jit__train_step_impl")[0]
    assert step == pytest.approx(sum(e.dur for e in runs) / 4 / 1e6)
    assert spec.load_reader(spec.ROOT, "sync_round_ms")(ctx) > 0
    # the model step's share over its own device time; the whole cycle's
    # over the traced window, which holds the rounds and the gaps too
    ctx.config, ctx.chips = cells.MAMBA, 1
    ctx.program = SimpleNamespace(pods=2, rows=2, seq=32)
    ctx.traced.tokens = 4 * 2 * 2 * 32
    ctx.peaks = spec.load_peaks("TPU v5 lite")
    flops = costs.train_flops_per_token(cells.MAMBA, 32) * 2 * 2 * 32
    mfu = spec.load_reader(spec.ROOT, "train_mfu")(ctx)
    assert mfu == pytest.approx(100 * flops / (step / 1e3) / 197e12)
    whole = spec.load_reader(spec.ROOT, "step_mfu")(ctx)
    assert whole == pytest.approx(100 * flops * 4 / ctx.traced.seconds
                                  / 197e12)
    assert whole < mfu
    # no collective and no codec kernel ran: those readers find nothing
    assert spec.load_reader(spec.ROOT, "ring_permute_ms")(ctx) is None
    assert spec.load_reader(spec.ROOT, "codec_roofline")(ctx) is None


def test_unknown_device_kind_fails():
    assert spec.load_peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        spec.load_peaks("TPU v9 imaginary")
