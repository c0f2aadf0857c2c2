"""The scope reduction on small traces recorded on the CPU backend
(``record_scopes.py``), each holding the compiled HLO of its programs:
``ga``, the ASGD-GA int8 codec with error feedback on a two-device pod
mesh, four train steps and two rounds, whose train step runs as two
executables (after a train step and after a round); ``ma``, model
averaging stacked on one device, four train steps and one round."""
import gzip
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest
from jax.profiler import ProfileData

from chipbench import run, scopes, spec, trace

DATA = Path(__file__).parent / "data"
PROGRAMS = ("jit__train_step_impl", "jit__sync_step_impl")
SCOPE_READERS = ("train_fwd_ms", "train_bwd_ms", "train_update_ms",
                 "sync_encode_ms", "sync_ef_ms", "sync_ring_ms",
                 "sync_apply_ms")
ROUNDS = {"ga": 2, "ma": 1}
# what the eight scope readers read from the committed traces before each
# top-level operation kept its whole ``op_name``
PINNED = {
    "ga": {"train_fwd_ms": 4.1016975, "train_bwd_ms": 8.123152875,
           "train_update_ms": 0.612272375, "sync_encode_ms": 59.29687125,
           "sync_ef_ms": 0.9700865, "sync_ring_ms": 5.1231335,
           "sync_apply_ms": 1.64062875, "trainer_idle_ms": 0.54110475},
    "ma": {"train_fwd_ms": 5.56883475, "train_bwd_ms": 12.512004,
           "train_update_ms": 0.225989, "sync_encode_ms": None,
           "sync_ef_ms": None, "sync_ring_ms": None,
           "sync_apply_ms": 0.167349, "trainer_idle_ms": 0.5374905}}


def unpack(tmp: Path, raw: bytes) -> Path:
    """``raw`` as a run's trace directory under ``tmp``."""
    (tmp / "plugins").mkdir(parents=True)
    (tmp / "plugins" / "run.xplane.pb").write_bytes(raw)
    return tmp


def context(tr):
    lo, hi = tr.window()
    return SimpleNamespace(trace=tr, traced=SimpleNamespace(
        lo=lo, hi=hi, steps=4, rounds=2, seconds=(hi - lo) / 1e9))


def raw_of(name):
    return gzip.decompress((DATA / f"cpu_scopes_{name}.xplane.pb.gz")
                           .read_bytes())


@pytest.fixture(scope="module", params=sorted(ROUNDS))
def traced(request, tmp_path_factory):
    name = request.param
    raw = raw_of(name)
    tr = trace.from_profile(ProfileData.from_serialized_xspace(raw))
    tmp = unpack(tmp_path_factory.mktemp(name), raw)
    ctx = context(tr)
    ctx.traced.rounds = ROUNDS[name]
    return name, tr, scopes.load(tr, str(tmp)), scopes.hlo_programs(raw), \
        ctx


@pytest.fixture
def reading(traced, monkeypatch, tmp_path):
    """The run's readers as ``run.py`` calls them, on the trace in a run's
    trace directory."""
    name, tr, _, _, ctx = traced
    monkeypatch.setattr(scopes, "TRACE_DIR",
                        str(unpack(tmp_path, raw_of(name))))
    return lambda m: spec.load_reader(spec.ROOT, m)(ctx)


def test_trace_dir_is_the_runs():
    assert Path(scopes.TRACE_DIR) == run.TRACE_DIR


def test_spans_and_programs(traced):
    name, tr, sc, hlo, _ = traced
    assert set(PROGRAMS) <= {mod for mod, _ in hlo.values()}
    chips = 2 if name == "ga" else 1
    assert tr.devices == sc.devices == list(range(chips))
    names = [s.name for s in sc.program_spans]
    assert names.count("repro.train_step") == 4
    assert names.count("repro.maybe_sync") == 4
    assert names.count("repro.sync_round") == ROUNDS[name]
    # the harness's own spans are read as before
    assert sorted({s.name for s in tr.spans}) == [
        "bench.batch", "bench.block", "bench.loss_read", "bench.maybe_sync",
        "bench.train_step"]


def test_each_execution_is_read_against_its_own_program(traced):
    name, tr, _, hlo, _ = traced
    runs = [r for r in scopes.executions(ProfileData.from_serialized_xspace(
        raw_of(name))) if r[3] in PROGRAMS]
    named = scopes.op_names(tr, runs, hlo)
    # the window runs the train step as two executables on a pod mesh
    # (after a train step and after a round), as one stacked
    train = {pid for *_, mod, pid in runs if mod == PROGRAMS[0]}
    assert len(train) == (2 if name == "ga" else 1)
    for dev, lo, hi, mod, pid in runs:
        assert hlo[pid][0] == mod
        names = scopes.hlo_op_names(hlo[pid][1])
        run = [op for op in tr.ops if op.device == dev and
               op.module == mod and lo <= op.start < hi]
        assert run and all(op.name in names for op in run), (mod, pid)
        assert all(named[id(op)] == names[op.name] for op in run)
    # every operation of the two programs is read
    assert len(named) == sum(op.module in PROGRAMS for op in tr.ops)


@pytest.mark.parametrize("program", PROGRAMS)
def test_scope_time_is_conserved(traced, program):
    _, tr, sc, _, _ = traced
    top = [op for op in scopes.top_level(tr) if op.module == program]
    split = scopes.split_ns(sc, program)
    assert sum(split.values()) == sum(op.dur for op in top)
    scoped = sum(v for k, v in split.items() if k)
    assert scoped > 0.5 * sum(split.values())
    # nested ops (while bodies) are left to their parent
    assert len(top) < sum(1 for op in tr.ops if op.module == program)


def test_readers(traced, reading):
    name, tr, sc, _, ctx = traced
    values = {m: reading(m) for m in SCOPE_READERS + ("trainer_idle_ms",)}
    absent = () if name == "ga" else ("sync_encode_ms", "sync_ef_ms",
                                      "sync_ring_ms")
    for m, v in values.items():
        if m in absent:
            assert v is None, m
        else:
            assert v is not None and v > 0, m
    # each is its scope's top-level time per step or round and chip
    chips = len(tr.devices)
    split = scopes.split_ns(sc, "jit__sync_step_impl")
    assert values["sync_apply_ms"] == pytest.approx(
        split["sync_apply"] / chips / ctx.traced.rounds / 1e6)
    split = scopes.split_ns(sc, "jit__train_step_impl")
    assert values["train_bwd_ms"] == pytest.approx(
        split[scopes.BACKWARD] / chips / 4 / 1e6)
    # idle inside the trainer's spans is idle inside the traced window
    lo, hi = ctx.traced.lo, ctx.traced.hi
    idle = sum(hi - lo - trace.busy_ns(tr, d, lo, hi) for d in tr.devices)
    assert values["trainer_idle_ms"] < idle / chips / 4 / 1e6


def test_readers_read_as_pinned(traced, reading):
    name = traced[0]
    assert {m: reading(m) for m in PINNED[name]} == PINNED[name]


def test_named_ms_reads_the_scopes(traced):
    """``named_ms`` on a scope of ``SCOPES`` reads at least what
    ``scope_ms`` gives it: ``scope_ms`` gives an op only its first scope,
    and leaves out an op lying inside another that does not hold it (on
    the CPU, ops that run side by side); ``train_forward`` split by
    ``transpose(`` gives the two passes."""
    _, _, sc, _, _ = traced
    pairs = [(scopes.named_ms(sc, s, 1, backward=False),
              scopes.scope_ms(sc, s, 1)) for s in scopes.SCOPES]
    pairs += [(scopes.named_ms(sc, "train_forward", 1, backward=True),
               scopes.scope_ms(sc, scopes.BACKWARD, 1))]
    for named, first in pairs:
        assert (named is None) == (first is None)
        assert first is None or named >= first
    assert scopes.named_ms(sc, "no_such_scope", 1, backward=False) is None
    # a component matches whole: a prefix of a scope's name is none
    assert scopes.named_ms(sc, "train_forw", 1, backward=False) is None


def test_named_ms_reads_a_scope_nested_under_train_forward(tmp_path):
    """A model's own ``jax.named_scope`` inside the body of its layer loop,
    under ``train_forward``, traced on the CPU: its forward and backward
    times are read, and each is a part of the pass it lies in."""
    import jax.numpy as jnp
    from repro.core.sync import SyncConfig
    from repro.training.trainer import Trainer, TrainerConfig

    V, D, F, L = 64, 32, 128, 4

    def init(key):
        k1, k2, k3 = jax.random.split(key, 3)
        return {"embed": jax.random.normal(k1, (V, D)) * 0.1,
                "w_in": jax.random.normal(k2, (L, D, F)) * 0.1,
                "w_out": jax.random.normal(k3, (L, F, D)) * 0.1}

    def layer(x, w):
        with jax.named_scope("mlp_experts"):
            h = jnp.square(jax.nn.relu(x @ w[0])) @ w[1]
        return x + h, None

    def loss(p, batch):
        x, _ = jax.lax.scan(layer, p["embed"][batch["tokens"]],
                            (p["w_in"], p["w_out"]))
        logits = x @ p["embed"].T
        gold = jnp.take_along_axis(logits, batch["labels"][..., None],
                                   axis=-1)[..., 0]
        return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold), {}

    tr = Trainer(loss, init, TrainerConfig(n_pods=2, optimizer="sgd",
                                           lr=0.01,
                                           sync=SyncConfig("ama", 1)))
    state = tr.init_state(jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 4, 65), 0, V)
    batch = {"tokens": tokens[..., :-1], "labels": tokens[..., 1:]}
    state = tr.maybe_sync(tr.train_step(state, batch)[0], 0)
    steps = 2
    with jax.profiler.trace(str(tmp_path)):
        for step in range(steps):
            state, _ = tr.train_step(state, batch)
            state = tr.maybe_sync(state, step)
        jax.block_until_ready(state)
    raw = next(tmp_path.glob("**/*.xplane.pb")).read_bytes()
    sc = scopes.from_xspace(trace.from_profile(
        ProfileData.from_serialized_xspace(raw)), raw)
    fwd = scopes.named_ms(sc, "mlp_experts", steps, backward=False)
    bwd = scopes.named_ms(sc, "mlp_experts", steps, backward=True)
    assert 0 < fwd <= scopes.scope_ms(sc, "train_forward", steps)
    assert 0 < bwd <= scopes.scope_ms(sc, scopes.BACKWARD, steps)


def test_named_ms_counts_an_op_once_and_reads_loop_bodies():
    """On a chip a loop's body runs inside the loop's own event, which
    top-level counting keeps alone: a scope inside the body of a loop that
    does not hold it is read from the body's ops, and an op inside one
    that holds the scope too is counted with it."""
    def op(name, start, dur, op_name):
        return trace.Event(0, name, start, dur, "jit__train_step_impl"), \
            op_name

    fwd = "jit(f)/vmap(jvp(train_forward))"
    bwd = "jit(f)/vmap(transpose(jvp(train_forward)))"
    ops = [op("while.1", 0, 100, fwd + "/while"),
           op("dot.1", 10, 30, fwd + "/while/body/moe_experts/dot_general"),
           op("fusion.1", 50, 20, fwd + "/while/body/moe_experts/mul"),
           op("fusion.2", 52, 5, fwd + "/while/body/moe_experts/add"),
           op("fusion.3", 80, 10, fwd + "/while/body/norm/mul"),
           op("while.2", 200, 300, bwd + "/while"),
           op("dot.2", 210, 60, bwd + "/while/body/moe_experts/dot_general")]
    sc = scopes.Scoped(devices=[0], ops=ops)
    assert [e.name for e, _ in sc.top] == ["while.1", "while.2"]
    assert scopes.named_ms(sc, "moe_experts", 1, backward=False) == 50e-6
    assert scopes.named_ms(sc, "moe_experts", 1, backward=True) == 60e-6
    assert scopes.named_ms(sc, "moe_experts", 2, backward=True) == 30e-6
    assert scopes.named_ms(sc, "train_forward", 1, backward=False) == \
        scopes.scope_ms(sc, "train_forward", 1) == 100e-6
    assert scopes.named_ms(sc, "norm", 1, backward=False) == 10e-6
    assert scopes.named_ms(sc, "norm", 1, backward=True) is None
    assert scopes.named_ms(sc, "moe", 1, backward=False) is None


def test_scope_of():
    fwd = "jit(_train_step_impl)/vmap(jvp(train_forward))/while/body/dot"
    assert scopes.scope_of(fwd) == "train_forward"
    assert scopes.scope_of(fwd.replace("vmap(jvp(", "vmap(transpose(jvp(")
                          .replace("))/", ")))/")) == scopes.BACKWARD
    assert scopes.scope_of("jit(f)/sync_encode/sync_ef/mul") == "sync_encode"
    assert scopes.scope_of("jit(f)/sync_efx/mul") == ""
    assert scopes.scope_of("") == ""


def test_fusion_takes_its_roots_op_name():
    text = "\n".join([
        "HloModule m, is_scheduled=true",
        "",
        "%fused_a (p: f32[2]) -> f32[2] {",
        "  %p = f32[2]{0} parameter(0)",
        '  %m.1 = f32[2]{0} multiply(%p, %p), '
        'metadata={op_name="x/sync_ef/mul"}',
        "  ROOT %c.1 = f32[2]{0} convert(%m.1)",
        "}",
        "",
        "ENTRY %main (a: f32[2]) -> f32[2] {",
        "  %a = f32[2]{0} parameter(0)",
        "  %f = f32[2]{0} fusion(%a), kind=kLoop, calls=%fused_a",
        '  ROOT %g = f32[2]{0} fusion(%f), kind=kLoop, calls=%fused_a, '
        'metadata={op_name="x/sync_apply/add"}',
        "}"])
    names = scopes.hlo_op_names(text)
    assert names["f"] == "x/sync_ef/mul"
    assert names["g"] == "x/sync_apply/add"
    assert names["a"] == ""


def test_compiler_ops_take_the_nearest_traced_op_name():
    text = "\n".join([
        "HloModule m, is_scheduled=true",
        "",
        "ENTRY %main (a: f32[4], b: f32[4]) -> (f32[4], f32[4]) {",
        '  %a = f32[4]{0} parameter(0), metadata={op_name="state.a"}',
        '  %b = f32[4]{0} parameter(1), metadata={op_name="state.b"}',
        '  %c = f32[4]{0} copy(%a), metadata={op_name="state.a"}',
        "  %d = f32[4]{0} copy(%c)",
        '  %e = f32[4]{0} multiply(%d, %d), '
        'metadata={op_name="jit(f)/sync_apply/mul"}',
        "  %k = f32[4]{0} copy(%e)",
        "  %p = f32[4]{0} copy(%b)",
        "  ROOT %t = (f32[4]{0}, f32[4]{0}) tuple(%k, %p)",
        "}"])
    names = scopes.hlo_op_names(text)
    # a relayout in front of a traced op takes that op's scope, through
    # other copies and past an argument's path
    assert scopes.scope_of(names["c"]) == "sync_apply"
    assert scopes.scope_of(names["d"]) == "sync_apply"
    # one behind it, with no traced user, takes its producer's
    assert scopes.scope_of(names["k"]) == "sync_apply"
    # a copy of an argument passed through to the outputs stays unscoped
    assert scopes.scope_of(names["p"]) == ""


def test_readers_leave_a_program_without_scopes_out(monkeypatch, tmp_path):
    # the benchmark's first trace, of a program with no scopes or spans
    raw = (DATA / "cpu_trace.xplane.pb").read_bytes()
    monkeypatch.setattr(scopes, "TRACE_DIR", str(unpack(tmp_path, raw)))
    ctx = context(trace.from_profile(ProfileData.from_serialized_xspace(
        raw)))
    for m in SCOPE_READERS + ("trainer_idle_ms",):
        assert spec.load_reader(spec.ROOT, m)(ctx) is None, m


def test_trace_holds_the_compiled_programs(tmp_path):
    import gc

    from repro.configs import get_arch
    from repro.core.sync import SyncConfig
    from repro.launch.context import wrap_loss
    from repro.models.registry import get_model_fns
    from repro.training.trainer import Trainer, TrainerConfig

    # the trace holds every executable alive in the process: let those of
    # earlier tests that are garbage go first
    gc.collect()
    arch = get_arch("mamba2-1.3b")
    cfg = arch.smoke
    fns = get_model_fns(arch.module)
    tr = Trainer(wrap_loss(fns, cfg), lambda k: fns.init_params(k, cfg),
                 TrainerConfig(n_pods=2, optimizer="sgd", lr=0.01,
                               sync=SyncConfig("ama", 1)))
    state = tr.init_state(jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 2, 17), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens[..., :-1], "labels": tokens[..., 1:]}
    compiled = tr.program_hlo(state, batch)
    with jax.profiler.trace(str(tmp_path)):
        state, _ = tr.train_step(state, batch)
        jax.block_until_ready(tr.maybe_sync(state, 0))
    path, = tmp_path.glob("**/*.xplane.pb")
    held = scopes.hlo_programs(path.read_bytes())
    # the executables the trace holds carry the compiled programs'
    # instructions and op_names
    for mod, texts in compiled.items():
        names = [scopes.hlo_op_names(t) for t in texts]
        ran = [scopes.hlo_op_names(t) for m, t in held.values() if m == mod]
        assert ran and all(n in names for n in ran), mod
