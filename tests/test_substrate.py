"""Substrate tests: optimizers, data pipeline, checkpointing, sharding rules."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.checkpoint import checkpoint as ckpt
from repro.data.pipeline import (GeoDataset, TokenStream,
                                 synthetic_classification)
from repro.optim.optimizers import (adamw, clip_by_global_norm, global_norm,
                                    momentum, sgd, warmup_cosine_schedule)
from repro.sharding.rules import LA, logical_to_spec, spec_tree_for_params

# ------------------------------------------------------------------ optim


def _quadratic_opt(opt, steps=200, lr=0.1):
    params = {"x": jnp.asarray([5.0, -3.0]), "y": jnp.asarray([[2.0]])}
    target = jax.tree.map(jnp.zeros_like, params)
    state = opt.init(params)
    for _ in range(steps):
        grads = jax.tree.map(lambda p, t: p - t, params, target)
        params, state = opt.update(grads, state, params, jnp.float32(lr))
    return float(global_norm(params))


@pytest.mark.parametrize("opt,lr", [(sgd(), 0.1), (momentum(0.9), 0.05),
                                    (adamw(), 0.05)])
def test_optimizers_minimize_quadratic(opt, lr):
    assert _quadratic_opt(opt, lr=lr) < 1e-2


def test_momentum_bf16_state_dtype():
    opt = momentum(state_dtype="bfloat16")
    params = {"x": jnp.ones((4,), jnp.float32)}
    st = opt.init(params)
    assert st["x"].dtype == jnp.bfloat16
    _, st2 = opt.update(params, st, params, jnp.float32(0.1))
    assert st2["x"].dtype == jnp.bfloat16


def test_clip_by_global_norm():
    tree = {"a": jnp.full((4,), 10.0)}
    clipped = clip_by_global_norm(tree, 1.0)
    assert float(global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)
    small = {"a": jnp.full((4,), 0.01)}
    np.testing.assert_allclose(np.asarray(clip_by_global_norm(small, 1.0)["a"]),
                               np.asarray(small["a"]))


def test_warmup_cosine():
    sched = warmup_cosine_schedule(1.0, warmup=10, total=100)
    assert float(sched(0)) == 0.0
    assert float(sched(10)) == pytest.approx(1.0)
    assert float(sched(100)) == pytest.approx(0.0, abs=1e-6)
    assert float(sched(5)) == pytest.approx(0.5)


# ------------------------------------------------------------------- data


def test_token_stream_deterministic_and_sharded():
    s0 = TokenStream(vocab_size=128, seq_len=16, batch_size=4, seed=1, shard=0)
    s0b = TokenStream(vocab_size=128, seq_len=16, batch_size=4, seed=1, shard=0)
    s1 = TokenStream(vocab_size=128, seq_len=16, batch_size=4, seed=1, shard=1)
    b0, b0b, b1 = s0.batch(3), s0b.batch(3), s1.batch(3)
    np.testing.assert_array_equal(b0["tokens"], b0b["tokens"])
    assert not np.array_equal(b0["tokens"], b1["tokens"])
    # labels are the shifted tokens
    np.testing.assert_array_equal(b0["labels"][:, :-1], b0["tokens"][:, 1:])
    assert b0["tokens"].max() < 128


def test_token_stream_structured_learnable():
    s = TokenStream(vocab_size=64, seq_len=64, batch_size=8, structured=True)
    b = s.batch(0)
    # ~90% of transitions follow next = (3 tok + 1) % V
    match = np.mean((3 * b["tokens"][:, :-1] + 1) % 64 == b["tokens"][:, 1:])
    assert match > 0.8


def test_geo_partition_ratio_and_coverage():
    data = synthetic_classification(1000, (4,), 3, feature_vocab=50)
    geo = GeoDataset.partition(data, ["a", "b", "c"], [2, 1, 1], seed=0)
    sizes = geo.sizes()
    assert sum(sizes.values()) == 1000
    assert sizes["a"] == 500 and sizes["b"] == 250
    # shards are disjoint and cover everything (check by multiset of labels)
    ys = np.concatenate([s.data["y"] for s in geo.shards])
    np.testing.assert_array_equal(np.sort(ys), np.sort(data["y"]))


def test_geo_loader_draws_only_own_shard():
    data = {"x": np.arange(100)[:, None].astype(np.float32),
            "y": np.arange(100).astype(np.int32)}
    geo = GeoDataset.partition(data, ["a", "b"], [1, 1], seed=0)
    own = set(geo.shards[0].data["y"].tolist())
    loader = geo.loader("a", 16, seed=3)
    for _ in range(5):
        batch = next(loader)
        assert set(batch["y"].tolist()) <= own


# -------------------------------------------------------------- checkpoint


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "nested": {"b": jnp.ones((4,), jnp.bfloat16)}}
    ckpt.save(str(tmp_path), tree, step=7, metadata={"note": "x"})
    like = jax.tree.map(lambda x: jnp.zeros_like(x), tree)
    restored, step = ckpt.restore(str(tmp_path), like)
    assert step == 7
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.asarray(tree["a"]))
    assert restored["nested"]["b"].dtype == jnp.bfloat16


def test_checkpoint_shape_mismatch_raises(tmp_path):
    ckpt.save(str(tmp_path), {"a": jnp.ones((2,))})
    with pytest.raises(ValueError):
        ckpt.restore(str(tmp_path), {"a": jnp.ones((3,))})


def test_checkpoint_missing_leaf_raises(tmp_path):
    ckpt.save(str(tmp_path), {"a": jnp.ones((2,))})
    with pytest.raises(KeyError):
        ckpt.restore(str(tmp_path), {"zz": jnp.ones((2,))})


# ---------------------------------------------------------------- sharding


class _FakeMesh:
    axis_names = ("pod", "data", "model")
    class devices:  # noqa: D401
        shape = (2, 16, 16)
        size = 512


def test_logical_to_spec_divisibility_fallback():
    rules = {"heads": "model", "batch": ("pod", "data"), "kv": "model"}
    spec = logical_to_spec((6, 32), ("heads", "batch"), rules, _FakeMesh())
    # 6 heads don't divide 16 -> replicated; 32 batch over pod*data
    assert spec == P(None, ("pod", "data"))
    spec = logical_to_spec((64, 31), ("heads", "batch"), rules, _FakeMesh())
    assert spec == P("model", None)   # 31 indivisible -> dropped


def test_logical_to_spec_no_duplicate_axis():
    rules = {"cache_seq": "model", "kv_heads": "model"}
    spec = logical_to_spec((32768, 16), ("cache_seq", "kv_heads"),
                           rules, _FakeMesh())
    assert spec == P("model", None)   # first dim wins the axis


def test_spec_tree_for_params():
    tree = {"w": LA(("heads", None)), "b": LA((None,))}
    ab = {"w": jax.ShapeDtypeStruct((32, 8), jnp.float32),
          "b": jax.ShapeDtypeStruct((8,), jnp.float32)}
    specs = spec_tree_for_params(tree, ab, {"heads": "model"}, _FakeMesh())
    assert specs["w"] == P("model", None)
    assert specs["b"] == P(None)


# ------------------------------------------------------------ mesh / cache


def test_pod_mesh_spans_present_devices_with_auto_axes():
    from jax.sharding import AxisType

    from repro.launch.mesh import make_pod_mesh

    mesh = make_pod_mesh()
    assert mesh.axis_names == ("pod", "data", "model")
    assert mesh.devices.shape == (len(jax.devices()), 1, 1)
    assert all(t == AxisType.Auto for t in mesh.axis_types)


def test_compile_cache_dir_env_wins_else_fixed_repo_path(monkeypatch):
    from repro.launch import cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert cache.enable_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == before   # left to JAX
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = cache.enable_compile_cache()
        assert path == str(cache.REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == path
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(root, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
