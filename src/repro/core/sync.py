"""Inter-pod model-synchronization strategies (paper §III.C) on SPMD/TPU.

Representation: every training-state leaf carries a leading ``pod`` dimension
of size ``n_pods`` (the number of cloud partitions), sharded over the
``"pod"`` mesh axis.  The per-pod train step is ``jax.vmap``-ed over that
dimension, and the paper's WAN synchronization primitives become array ops on
it, which XLA SPMD lowers to exactly the right collectives:

- ``jnp.roll(x, shift, axis=0)``  -> ``collective-permute`` over ``"pod"`` —
  the TPU analogue of the paper's one-PS-to-one-peer gRPC send (the paper:
  "Cloudless-Training limits each PS to send its state to only one other PS
  each time").
- ``jnp.mean(x, axis=0)``         -> ``all-reduce`` over ``"pod"`` — the
  barrier average of SMA (and the per-step reduction of the ASGD baseline).

Strategies (paper §III.C):

- **ASGD (baseline)** — "simple asynchronous SGD", sync frequency 1: the
  gradient is averaged across pods *every* step.
- **ASGD-GA** — gradients are accumulated locally for ``interval`` steps; at
  a sync point each pod ships the *accumulated* gradient to one ring peer and
  applies the received gradient as an extra SGD update (receiver-side SGD per
  the paper).  Between syncs pods run fully independently; under SPMD the
  asynchrony becomes a bounded one-round staleness window.
- **AMA** — inter-PS model averaging, asynchronous pattern: every
  ``interval`` steps each pod averages parameters with one ring peer
  (gossip averaging; pairwise == global for the paper's 2-cloud setup).
- **SMA** — synchronous pattern: global barrier average over all pods
  (paper Fig 11: best accuracy, highest sync cost).

Beyond-paper option: ``compress_topk`` ships only the top-k fraction of
accumulated-gradient entries (the paper cites DGC/top-K as the complementary
WAN-optimization family but does not implement it); see
``repro.kernels.topk_compress``.  It compounds with ASGD-GA's frequency
reduction to cut inter-pod bytes further.

With ``quantize_int8`` the top-k path upgrades to the **fused WAN codec**
(``repro.kernels.wan_codec``), the full payload pipeline:

  bucket -> top-k -> int8 -> ring -> decode -> error feedback

- **bucket**: the accumulated-gradient pytree is packed once into a single
  contiguous ``(n_pods, N)`` buffer, so compression is a handful of fused
  dispatches instead of one per leaf.  Under ``bucket_policy=
  "layer-class"`` the buffer is *grouped by layer class* (embed / norm /
  dense / MoE — :class:`BucketSpec` classifies leaves by parameter path),
  each group a contiguous segment with its OWN ``(compress_topk,
  value_dtype)`` knobs and EF telemetry: aggressive compression where the
  gradient statistics make it free, conservative where it hurts.
- **top-k + int8**: a single-pass Pallas kernel selects the block-local
  top-k and quantizes the winners to int8 with per-block scales — payload
  bytes drop to ``~0.75 * compress_topk`` of dense fp32 (int8 value + u16
  local index per kept element, vs the fp32+int32 pairs of the unquantized
  path); see ``SyncConfig.payload_mb``.
- **ring**: the *compact* (q, idx, scales) triple is what rolls over the
  pod axis (collective-permute) — never the dense buffer.  With
  ``overlap_chunks > 1`` the bucket is split on codec-block boundaries and
  the permute of chunk i is data-independent of the encode of chunk i+1,
  so the WAN transfer hides behind the remaining compression work (TAAR's
  overlap, arXiv:2404.11352); chunking is bit-exact vs the unchunked path.
- **error feedback** (``error_feedback=True``): each pod keeps the residual
  ``message - decode(encode(message))`` — everything top-k dropped plus the
  quantization rounding — and re-injects it into the next interval's
  message (EF-SGD semantics), so aggressive compression stops costing
  convergence instead of silently discarding gradient mass.

Because the representation is pure ``jnp`` on a stacked dimension, the same
code runs (a) multi-pod on TPU via sharding, and (b) as a faithful multi-cloud
*emulation* on a single CPU device — which is how the convergence-parity
tests reproduce the paper's Figs 7/9/10 accuracy results for real.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, replace as _dc_replace
from typing import (Any, Dict, Mapping, NamedTuple, Optional, Sequence,
                    Tuple, Union)

import jax
import jax.numpy as jnp
import numpy as np
from math import prod as np_prod

Pytree = Any

STRATEGIES = ("asgd", "asgd_ga", "ama", "sma", "asp")

# the codec's precision ladder, least -> most aggressive.  Tier 0 (fp32) is
# "codec off": sparse fp32 (value+index pairs) or fully dense.  Wire bytes
# per kept element: fp32 4+4 (int32 index), int8/fp8 1+2 (u16 block-local
# index), int4 0.5+2 — plus one fp32 scale per codec block on tiers >= 1.
CODEC_TIERS = ("fp32", "int8", "fp8", "int4")
VALUE_DTYPES = CODEC_TIERS[1:]
_VALUE_BYTES = {"int8": 1.0, "fp8": 1.0, "int4": 0.5}


# ---------------------------------------------------------------------------
# bucket groups: layer-class partitioning of the sync payload
# ---------------------------------------------------------------------------
#
# Gradient statistics are wildly non-uniform across layer classes: embedding
# rows are touched sparsely (top-k is nearly free), norms/biases are tiny but
# convergence-critical (compression buys nothing and hurts), MoE expert
# blocks see token-routed sparsity, and the attention/MLP dense bulk is where
# the bytes actually live.  The layer-class bucket policy splits the one flat
# codec bucket into named groups so each can run its own (top-k x dtype)
# aggression — the per-tensor adaptation network-aware geo-distributed
# systems converge on (TAAR, arXiv:2404.11352; HeterPS, arXiv:2111.10635).

BUCKET_CLASSES = ("embed", "norm", "dense", "moe")
BUCKET_POLICIES = ("single", "layer-class")


@dataclass(frozen=True)
class BucketSpec:
    """Classifies pytree leaves into named bucket groups.

    A leaf's parameter *path* (``jax.tree_util.keystr``) is matched against
    per-group substring patterns, first hit wins (``patterns`` order is the
    precedence order — MoE before embed so ``moe/router`` lands in the
    expert group).  Pattern-less leaves fall through on shape: rank <= 1
    per-pod tensors (biases, norm scales, per-feature vectors) go to
    ``vector_bucket``, everything else to ``fallback``.  The default
    patterns are the same path vocabulary ``sharding/rules.py`` keys its
    logical axes on (vocab/embed, experts/router, heads/d_ff dense).

    The table is user-definable: a ``SyncConfig`` carries its spec
    (``bucket_spec``), the launcher parses one from ``--bucket-patterns``
    (:meth:`parse`), and every downstream consumer — layout, validation,
    per-bucket knobs, the adaptive controllers — follows the spec's
    ``names``.  The spec is frozen/hashable so it rides inside the
    jit-static ``SyncConfig`` without disturbing the compiled-sync cache."""

    names: Tuple[str, ...] = BUCKET_CLASSES
    patterns: Tuple[Tuple[str, Tuple[str, ...]], ...] = (
        ("moe", ("moe", "expert", "router")),
        ("embed", ("embed", "emb", "vocab", "wte", "wpe", "lm_head",
                   "tok_", "token")),
        ("norm", ("norm", "ln1", "ln2", "rms", "bias", "scale")),
    )
    vector_bucket: str = "norm"
    fallback: str = "dense"

    def __post_init__(self):
        if not self.names or len(set(self.names)) != len(self.names):
            raise ValueError("bucket spec needs non-empty, unique names, "
                             f"got {self.names}")
        for name, subs in self.patterns:
            if name not in self.names:
                raise ValueError(
                    f"bucket spec pattern group {name!r} is not one of its "
                    f"names {self.names}")
            if not subs:
                raise ValueError(f"bucket spec group {name!r} has an empty "
                                 f"pattern list")
        for role, name in (("vector_bucket", self.vector_bucket),
                           ("fallback", self.fallback)):
            if name not in self.names:
                raise ValueError(
                    f"bucket spec {role} {name!r} is not one of its names "
                    f"{self.names}")

    def classify(self, path: str, inner_ndim: int) -> str:
        """Bucket name for one leaf (``inner_ndim`` excludes the pod dim)."""
        low = path.lower()
        for name, subs in self.patterns:
            if any(s in low for s in subs):
                return name
        return self.vector_bucket if inner_ndim <= 1 else self.fallback

    @classmethod
    def parse(cls, spec: str) -> "BucketSpec":
        """Build a spec from the launcher's ``--bucket-patterns`` string.

        Named presets: ``default`` (the four-class table) and
        ``moe-router`` (:data:`MOE_ROUTER_BUCKET_SPEC` — routers split out
        of the expert group).  Otherwise, semicolon-separated
        ``name=sub1|sub2`` pattern groups in precedence order, plus the
        optional directives ``vector=name`` / ``fallback=name`` (defaults:
        ``norm`` / ``dense`` if those names exist, else the last group /
        the first pattern-less group)::

            router=router;moe=moe|expert;embed=embed|vocab;norm=norm|bias;dense=

        Groups may be declared pattern-less (``dense=``) just to exist as
        a fallback target."""
        key = spec.strip().lower()
        if key in ("", "default"):
            return DEFAULT_BUCKET_SPEC
        if key == "moe-router":
            return MOE_ROUTER_BUCKET_SPEC
        names: list = []
        patterns: list = []
        vector = fallback = None
        for entry in spec.split(";"):
            entry = entry.strip()
            if not entry:
                continue
            name, eq, subs = entry.partition("=")
            name = name.strip()
            if not eq:
                raise ValueError(
                    f"--bucket-patterns entry {entry!r} is not "
                    f"'name=sub1|sub2' (or 'vector=name'/'fallback=name')")
            if name == "vector":
                vector = subs.strip()
                continue
            if name == "fallback":
                fallback = subs.strip()
                continue
            if name not in names:
                names.append(name)
            pats = tuple(s.strip().lower() for s in subs.split("|")
                         if s.strip())
            if pats:
                patterns.append((name, pats))
        if not names:
            raise ValueError(f"--bucket-patterns {spec!r} defines no bucket "
                             f"groups")
        for role, target in (("vector", vector), ("fallback", fallback)):
            if target is not None and target not in names:
                # refusing (not creating) catches a typoed group name —
                # a phantom group would silently swallow every fallthrough
                # leaf while the declared group stays empty
                raise ValueError(
                    f"--bucket-patterns {role}={target!r} names an "
                    f"undeclared bucket group (declared: {tuple(names)}); "
                    f"declare it, e.g. '{target}='")
        vector = vector or ("norm" if "norm" in names else names[-1])
        # fallback default: 'dense' if declared, else the first
        # pattern-LESS group (declaring 'name=' with no patterns is the
        # documented way to create a catch-all), else the last group —
        # NEVER the first: groups are listed most-specific-first, and a
        # fallback into the most specific group would silently give every
        # unmatched dense matrix e.g. router-grade treatment
        if fallback is None:
            pattern_names = {n for n, _ in patterns}
            patternless = [n for n in names if n not in pattern_names]
            fallback = ("dense" if "dense" in names
                        else (patternless[0] if patternless else names[-1]))
        return cls(names=tuple(names), patterns=tuple(patterns),
                   vector_bucket=vector, fallback=fallback)


DEFAULT_BUCKET_SPEC = BucketSpec()

# the MoE recipe's spec: routers in their OWN group instead of riding the
# expert group.  Router gradients are dense and convergence-critical (they
# steer token routing; quantization error there mis-routes tokens), while
# expert blocks see token-routed sparsity that tolerates aggressive top-k —
# one (top-k, dtype) rung cannot serve both, which is why this table exists.
# Precedence: router patterns FIRST, so ``moe/router`` no longer falls to
# the ``moe`` group's broader patterns.
MOE_ROUTER_BUCKET_SPEC = BucketSpec(
    names=("embed", "norm", "dense", "moe", "router"),
    patterns=(
        ("router", ("router", "gating")),
        ("moe", ("moe", "expert")),
        ("embed", ("embed", "emb", "vocab", "wte", "wpe", "lm_head",
                   "tok_", "token")),
        ("norm", ("norm", "ln1", "ln2", "rms", "bias", "scale")),
    ))


@dataclass(frozen=True)
class BucketLayout:
    """Concrete partition of one stacked pytree into bucket groups.

    The grouped flat buffer concatenates leaves in ``order`` (stable: by
    bucket, then original ``jax.tree.leaves`` position), so every bucket
    group owns one contiguous ``(n_pods, N_g)`` segment —
    ``[offsets[g] : offsets[g] + sizes[g])`` — of the same ``(n_pods, N)``
    buffer the EF residual lives in.  For the ``"single"`` policy the order
    is the identity and the layout degenerates to the legacy one-bucket
    packing."""

    names: Tuple[str, ...]          # bucket group names, fixed order
    leaf_bucket: Tuple[int, ...]    # bucket index per leaf (original order)
    leaf_sizes: Tuple[int, ...]     # per-leaf flat width (per pod)
    order: Tuple[int, ...]          # leaf indices in packing order
    sizes: Tuple[int, ...]          # per-bucket segment width N_g
    offsets: Tuple[int, ...]        # per-bucket segment start

    @property
    def leaf_offsets(self) -> Tuple[int, ...]:
        """Offset of each (original-index) leaf in the grouped buffer."""
        off, out = 0, [0] * len(self.order)
        for i in self.order:
            out[i] = off
            off += self.leaf_sizes[i]
        return tuple(out)

    def segment(self, name: str) -> Tuple[int, int]:
        g = self.names.index(name)
        return self.offsets[g], self.sizes[g]


def bucket_layout(cfg: "SyncConfig", stacked_tree: Pytree,
                  spec: Optional[BucketSpec] = None) -> BucketLayout:
    """Partition ``stacked_tree`` (leading pod dim) per ``cfg.bucket_policy``.

    The pattern table defaults to the config's own ``bucket_spec`` (which
    the launcher's ``--bucket-patterns`` sets).  Host-side and shape-only:
    safe to call while tracing (it runs once per compile inside the jitted
    sync step)."""
    spec = spec if spec is not None else cfg.bucket_spec
    flat, _ = jax.tree_util.tree_flatten_with_path(stacked_tree)
    leaf_sizes = tuple(int(np_prod(x.shape[1:])) for _, x in flat)
    if cfg.bucket_policy == "single":
        names = ("all",)
        leaf_bucket = (0,) * len(flat)
        order = tuple(range(len(flat)))
    else:
        names = spec.names
        leaf_bucket = tuple(
            names.index(spec.classify(jax.tree_util.keystr(path),
                                      x.ndim - 1))
            for path, x in flat)
        order = tuple(sorted(range(len(flat)),
                             key=lambda i: (leaf_bucket[i], i)))
    sizes = tuple(sum(leaf_sizes[i] for i in range(len(flat))
                      if leaf_bucket[i] == g) for g in range(len(names)))
    offsets = tuple(sum(sizes[:g]) for g in range(len(names)))
    return BucketLayout(names=names, leaf_bucket=leaf_bucket,
                        leaf_sizes=leaf_sizes, order=order,
                        sizes=sizes, offsets=offsets)


def bucket_weights_of(cfg: "SyncConfig", stacked_tree: Pytree,
                      spec: Optional[BucketSpec] = None
                      ) -> Dict[str, float]:
    """Fraction of model elements per bucket group (sums to 1.0) — the
    weights :meth:`SyncConfig.payload_mb` uses for per-bucket accounting."""
    layout = bucket_layout(cfg, stacked_tree, spec)
    total = max(1, sum(layout.sizes))
    return {n: layout.sizes[g] / total for g, n in enumerate(layout.names)}


@dataclass(frozen=True)
class BucketOverride:
    """Per-bucket codec knobs; ``None`` inherits the global SyncConfig
    value.  Carried in ``SyncConfig.buckets`` (hashable, jit-static).

    ``codec_block`` tunes the block-local top-k granularity per bucket:
    embedding-class gradients are token-sparse (their mass clusters, so a
    *small* block keeps selection local and the per-block scale tight)
    while the dense bulk amortizes better under large blocks (fewer fp32
    scales on the wire — the ``1/block`` payload term)."""

    name: str
    compress_topk: Optional[float] = None
    value_dtype: Optional[str] = None
    codec_block: Optional[int] = None


@dataclass(frozen=True)
class SyncConfig:
    strategy: str = "asgd"
    interval: int = 1              # K — sync every K steps (1 for baseline)
    peer_shift: int = 1            # ring shift for the one-peer send; must be
    #   coprime with n_pods or the gossip ring decomposes into disjoint
    #   subrings that never reach consensus (property-tested)
    compress_topk: float = 0.0     # 0/1 = dense; else fraction of entries shipped
    ga_lr_scale: float = 1.0       # LR scale for the receiver-side SGD update
    asp_threshold: float = 0.01    # ASP: relative-significance threshold
    quantize_int8: bool = False    # fused WAN codec on (value_dtype picks the
    #   payload tier; the flag name is historical — the first tier was int8)
    value_dtype: str = "int8"      # codec payload tier: int8 | fp8 | int4
    error_feedback: bool = False   # EF-SGD: re-inject compression residual
    codec_block: int = 4096        # block-local top-k block size (codec path)
    overlap_chunks: int = 1        # >1: pipeline ring permute with encode
    bucket_policy: str = "single"  # "single": one flat codec bucket (legacy);
    #   "layer-class": partition the payload into BUCKET_CLASSES groups, each
    #   with its own (top-k, dtype) knobs and EF telemetry
    buckets: Tuple[BucketOverride, ...] = ()   # per-bucket knob overrides
    #   (layer-class only); unnamed buckets inherit the global knobs
    bucket_spec: BucketSpec = DEFAULT_BUCKET_SPEC   # the layer-class
    #   pattern table (user-definable via --bucket-patterns); frozen and
    #   hashable, so it is part of the jit-static config like every other
    #   codec knob

    def __post_init__(self):
        self._validate()

    def _validate(self) -> None:
        """Each knob gets its own precise error: a run configured with a
        silently-inert flag would train one way while its summary claims
        another, so every coupling is refused with the exact reason."""
        if self.strategy not in STRATEGIES:
            raise ValueError(f"unknown strategy {self.strategy!r}")
        if self.interval < 1:
            raise ValueError("interval must be >= 1")
        if self.overlap_chunks < 1:
            raise ValueError("overlap_chunks must be >= 1")
        if self.codec_block < 128 or self.codec_block > (1 << 16):
            raise ValueError("codec_block must be in [128, 65536] (local "
                             "indices ship as u16)")
        if self.value_dtype not in VALUE_DTYPES:
            raise ValueError(
                f"unknown value_dtype {self.value_dtype!r}: the codec's "
                f"payload tiers are {VALUE_DTYPES} (fp32 is codec-off)")
        if self.value_dtype != "int8" and not self.quantize_int8:
            raise ValueError(
                f"value_dtype={self.value_dtype!r} is inert without the "
                f"fused codec (quantize_int8=True): the run would ship "
                f"sparse/dense fp32 while its summary claims "
                f"{self.value_dtype}")
        if self.quantize_int8:
            if self.strategy != "asgd_ga":
                raise ValueError(
                    f"the fused codec (quantize_int8=True) compresses "
                    f"shipped accumulated gradients and therefore requires "
                    f"strategy='asgd_ga', not {self.strategy!r}")
            if not 0.0 < self.compress_topk < 1.0:
                raise ValueError(
                    f"the fused codec (quantize_int8=True) needs a top-k "
                    f"fraction 0 < compress_topk < 1, got "
                    f"{self.compress_topk} — without one the run would "
                    f"train dense while its summary claims "
                    f"{self.value_dtype}/EF")
        if self.error_feedback and not self.quantize_int8:
            raise ValueError("error_feedback requires the fused codec "
                             "(quantize_int8=True): the EF residual is "
                             "defined as what encode->decode lost")
        if self.overlap_chunks > 1 and not self.uses_codec:
            raise ValueError(
                "overlap_chunks > 1 requires the fused codec "
                "(strategy='asgd_ga', 0 < compress_topk < 1, "
                "quantize_int8=True): chunk pipelining only exists on the "
                "codec path")
        self._validate_buckets()

    def _validate_buckets(self) -> None:
        """Multi-bucket coupling checks.  Every message names the offending
        bucket group: a multi-bucket config has one line per group and a
        bare per-knob error would not say WHICH group is misconfigured."""
        if self.bucket_policy not in BUCKET_POLICIES:
            raise ValueError(
                f"unknown bucket_policy {self.bucket_policy!r}: choices are "
                f"{BUCKET_POLICIES}")
        if self.bucket_policy != "single" and not self.uses_codec:
            raise ValueError(
                "bucket_policy='layer-class' is inert without the fused "
                "codec (strategy='asgd_ga', 0 < compress_topk < 1, "
                "quantize_int8=True): only the codec path packs per-bucket "
                "payloads, so the run would train single-bucket while its "
                "summary claims per-bucket control")
        if not self.buckets:
            return
        if self.bucket_policy == "single":
            raise ValueError(
                f"bucket overrides ({', '.join(o.name for o in self.buckets)}"
                f") require bucket_policy='layer-class': under 'single' "
                f"there is one unnamed bucket and the overrides would be "
                f"silently ignored")
        seen = set()
        for ov in self.buckets:
            where = f"bucket {ov.name!r}: "
            if ov.name not in self.bucket_spec.names:
                raise ValueError(
                    where + f"unknown bucket group; the layer-class groups "
                    f"are {self.bucket_spec.names}")
            if ov.name in seen:
                raise ValueError(where + "duplicate override — each bucket "
                                         "group may be overridden once")
            seen.add(ov.name)
            if ov.compress_topk is not None and \
                    not 0.0 < ov.compress_topk < 1.0:
                raise ValueError(
                    where + f"compress_topk must be in (0, 1), got "
                    f"{ov.compress_topk} — a dense per-bucket payload has "
                    f"no codec selection to quantize")
            if ov.value_dtype is not None and \
                    ov.value_dtype not in VALUE_DTYPES:
                raise ValueError(
                    where + f"unknown value_dtype {ov.value_dtype!r}: the "
                    f"codec's payload tiers are {VALUE_DTYPES}")
            if ov.codec_block is not None and \
                    not 128 <= ov.codec_block <= (1 << 16):
                raise ValueError(
                    where + f"codec_block must be in [128, 65536] (local "
                    f"indices ship as u16), got {ov.codec_block}")

    # ------------------------------------------------------ bucket groups
    @property
    def bucket_names(self) -> Tuple[str, ...]:
        """Bucket group names in segment order (one unnamed group when the
        policy is ``"single"``)."""
        return (("all",) if self.bucket_policy == "single"
                else self.bucket_spec.names)

    def bucket_knobs(self, name: str) -> Tuple[float, str, int]:
        """Effective (compress_topk, value_dtype, codec_block) for one
        bucket group."""
        for ov in self.buckets:
            if ov.name == name:
                return (ov.compress_topk if ov.compress_topk is not None
                        else self.compress_topk,
                        ov.value_dtype if ov.value_dtype is not None
                        else self.value_dtype,
                        ov.codec_block if ov.codec_block is not None
                        else self.codec_block)
        return self.compress_topk, self.value_dtype, self.codec_block

    def for_bucket(self, name: str) -> "SyncConfig":
        """The effective single-bucket config governing one group's segment
        — what the codec dispatch and the payload math run with."""
        frac, dtype, block = self.bucket_knobs(name)
        return _dc_replace(self, compress_topk=frac, value_dtype=dtype,
                           codec_block=block, bucket_policy="single",
                           buckets=())

    @property
    def bucket_tiers(self) -> Tuple[int, ...]:
        """Per-bucket index into :data:`CODEC_TIERS` (segment order)."""
        return tuple(self.for_bucket(n).tier for n in self.bucket_names)

    @property
    def sends_gradients(self) -> bool:
        return self.strategy in ("asgd", "asgd_ga")

    @property
    def uses_codec(self) -> bool:
        """True when sync rounds run the fused bucket->top-k->quantize codec."""
        return (self.strategy == "asgd_ga" and self.quantize_int8
                and 0.0 < self.compress_topk < 1.0)

    @property
    def tier(self) -> int:
        """Index into :data:`CODEC_TIERS` (0 = fp32 / codec off)."""
        return CODEC_TIERS.index(self.value_dtype) if self.uses_codec else 0

    def payload_mb(self, model_mb: float,
                   measured_frac: Optional[float] = None,
                   bucket_weights: Optional[Mapping[str, float]] = None
                   ) -> float:
        """Per-sync WAN payload per pod (drives the simulator & roofline).

        Sparse fp32 ships (fp32 value, int32 index) pairs: ``2 * frac`` of
        dense.  The fused codec ships (value, u16 block-local index) pairs
        plus one fp32 scale per ``codec_block`` elements; value bytes per
        tier: int8/fp8 1, int4 0.5 (two nibble-packed codes per byte).  So
        int8/fp8 cost ``0.75 * frac + 1/codec_block`` of dense and int4
        ``0.625 * frac + 1/codec_block`` — >=8x below dense fp32 whenever
        ``frac <= 0.166`` (int8, default block) / ``frac <= 0.2`` (int4).
        For ASP pass the measured significant fraction (runtime-dependent);
        a nominal 30% is assumed otherwise (Gaia reports 10-50%).

        With ``bucket_weights`` (fraction of model elements per bucket,
        from :func:`bucket_weights_of`) a layer-class config is billed
        per bucket: each group's segment pays its *own* (top-k, dtype)
        rate.  Without weights the global knobs price the whole model —
        exact for "single", an approximation for an overridden
        layer-class config (callers that know the partition pass
        weights)."""
        if (bucket_weights is not None and self.uses_codec
                and self.bucket_policy != "single"):
            return sum(
                self.for_bucket(n).payload_mb(
                    model_mb * bucket_weights.get(n, 0.0))
                for n in self.bucket_names)
        if self.strategy == "asp":
            frac = measured_frac if measured_frac is not None else 0.3
            return model_mb * (2 * frac if frac < 1.0 else 1.0)
        if 0.0 < self.compress_topk < 1.0 and self.strategy == "asgd_ga":
            frac = self.compress_topk
            if self.quantize_int8:
                per_elem = (_VALUE_BYTES[self.value_dtype] + 2.0) / 4.0
                return model_mb * (frac * per_elem + 1.0 / self.codec_block)
            return model_mb * 2 * frac
        return model_mb


class SyncState(NamedTuple):
    ga_buffer: Pytree              # accumulated grads (ASGD-GA) or the
    #   reference params at the last sync (ASP), leading pod dim
    steps_since_sync: jnp.ndarray  # scalar int32
    significant_frac: jnp.ndarray  # ASP: fraction shipped at the last sync
    ef_residual: jnp.ndarray
    #   error-feedback residual, flat (n_pods, N) in *bucket-grouped* leaf
    #   order (what the codec dropped + quantization error, re-injected next
    #   sync); each bucket group owns one contiguous (n_pods, N_g) segment
    #   of it (see BucketLayout); (n_pods, 0) when the codec/EF path is off.
    #   Deliberately no default: a defaulted jnp array would be built at
    #   import time AND let stale 3-field constructor calls silently produce
    #   a wrong pod dim — ``init_sync_state`` is the way to build one
    tier: jnp.ndarray              # (n_buckets,) int32 indices into
    #   CODEC_TIERS — each bucket group's payload tier at the last sync
    #   (survives retunes/resizes, so logs and checkpoints can tell what
    #   the adaptive controller chose per bucket; length 1 under "single")
    msg_norm: jnp.ndarray          # (n_pods, n_buckets) L2 of the last
    #   codec sync's pre-compression message per bucket segment
    #   (accumulated grad avg + EF residual)
    resid_norm: jnp.ndarray        # (n_pods, n_buckets) L2 of the
    #   post-sync EF residual per bucket segment.  msg/resid norms are the
    #   adaptive controllers' per-bucket gradient-statistics inputs; zeros
    #   off the codec path


def init_sync_state(cfg: SyncConfig, stacked_params: Pytree) -> SyncState:
    """``stacked_params`` leaves have the leading pod dimension."""
    n_pods = jax.tree.leaves(stacked_params)[0].shape[0]
    if cfg.strategy == "asgd_ga":
        buf = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), stacked_params)
    elif cfg.strategy == "asp":
        buf = jax.tree.map(
            lambda p: p.astype(jnp.float32), stacked_params)
    else:
        buf = jax.tree.map(lambda p: jnp.zeros((0,), jnp.float32),
                           stacked_params)
    n_ef = (sum(x.size for x in jax.tree.leaves(stacked_params)) // n_pods
            if (cfg.uses_codec and cfg.error_feedback) else 0)
    nb = len(cfg.bucket_names)
    return SyncState(ga_buffer=buf,
                     steps_since_sync=jnp.zeros((), jnp.int32),
                     significant_frac=jnp.ones((), jnp.float32),
                     ef_residual=jnp.zeros((n_pods, n_ef), jnp.float32),
                     tier=jnp.asarray(cfg.bucket_tiers, jnp.int32),
                     msg_norm=jnp.zeros((n_pods, nb), jnp.float32),
                     resid_norm=jnp.zeros((n_pods, nb), jnp.float32))


# ---------------------------------------------------------------------------
# per-step hook (inside the jitted train step)
# ---------------------------------------------------------------------------


def on_step_gradients(cfg: SyncConfig, grads: Pytree, state: SyncState
                      ) -> Tuple[Pytree, SyncState]:
    """Process fresh per-pod gradients (leading pod dim, already averaged over
    the intra-pod data axis by the loss mean).  Returns (gradients for the
    local optimizer update, new sync state)."""
    n_pods = jax.tree.leaves(grads)[0].shape[0]
    bump = state._replace(steps_since_sync=state.steps_since_sync + 1)

    if cfg.strategy == "asgd" and n_pods > 1:
        # baseline: cross-pod all-reduce every step
        grads = jax.tree.map(
            lambda g: jnp.broadcast_to(jnp.mean(g, axis=0, keepdims=True),
                                       g.shape),
            grads)
        return grads, bump

    if cfg.strategy == "asgd_ga":
        buf = jax.tree.map(lambda b, g: b + g.astype(jnp.float32),
                           state.ga_buffer, grads)
        return grads, bump._replace(ga_buffer=buf)

    return grads, bump


# ---------------------------------------------------------------------------
# sync point (a separate jitted function, invoked every K host steps)
# ---------------------------------------------------------------------------


# --------------------------------------------------- bucketed WAN codec path


def _pack_stacked(tree: Pytree,
                  layout: Optional[BucketLayout] = None) -> jnp.ndarray:
    """Pack a stacked pytree into one contiguous (n_pods, N) bucket buffer.

    One concatenate amortizes the per-leaf compression dispatch the legacy
    path pays.  Without a layout, leaf order (jax.tree.leaves) defines the
    buffer order; with one, leaves are grouped by bucket (``layout.order``)
    so each bucket group is a contiguous segment — either way the result's
    order is the order ``ef_residual`` is stored in."""
    leaves = jax.tree.leaves(tree)
    if layout is not None:
        leaves = [leaves[i] for i in layout.order]
    return jnp.concatenate(
        [x.reshape(x.shape[0], -1).astype(jnp.float32) for x in leaves],
        axis=1)


def _unpack_stacked(flat: jnp.ndarray, like: Pytree,
                    layout: Optional[BucketLayout] = None) -> Pytree:
    """Inverse of :func:`_pack_stacked` against a reference pytree."""
    leaves, treedef = jax.tree.flatten(like)
    offsets = (layout.leaf_offsets if layout is not None else None)
    out, off = [], 0
    for i, x in enumerate(leaves):
        size = int(np_prod(x.shape[1:]))
        lo = offsets[i] if offsets is not None else off
        out.append(flat[:, lo:lo + size].reshape(x.shape))
        off += size
    return jax.tree.unflatten(treedef, out)


class ChunkPayload(NamedTuple):
    """One overlap chunk's compact wire triple — exactly what crosses the
    pod axis: quantized values (tier dtype; int4 already nibble-packed),
    u16 block-local indices, and per-block fp32 scales."""

    q: jnp.ndarray
    idx: jnp.ndarray       # uint16 on the wire (block-local, < 65536)
    scales: jnp.ndarray


class SyncPayloads(NamedTuple):
    """Output of the codec's *decide/pack* stage (jit-transparent pytree):
    the dense pre-compression message, its local reconstruction (what this
    pod's peer will decode — needed for the EF residual), and the
    per-bucket wire chunks a :class:`~repro.core.transport.WanTransport`
    ships.  Empty bucket groups are absent from ``chunks``."""

    flat: jnp.ndarray                               # (n_pods, N) message
    local: Optional[jnp.ndarray]                    # decode-at-sender (EF)
    chunks: Dict[str, Tuple[ChunkPayload, ...]]     # non-empty buckets


def _chunk_widths(cfg: SyncConfig, n_total: int) -> Tuple[int, ...]:
    """Static per-chunk dense widths of one bucket segment.

    Chunks split on codec-block boundaries, so the chunked selection is
    bit-identical to the unchunked one; host-side and shape-only, shared
    by encode and decode so both sides agree without shipping widths."""
    block = min(cfg.codec_block, max(1, n_total))
    nb = -(-n_total // block)
    n_chunks = max(1, min(cfg.overlap_chunks, nb))
    step = -(-nb // n_chunks) * block
    return tuple(min(step, n_total - lo) for lo in range(0, n_total, step))


def _per_pod(fn, *arrays):
    """``jax.vmap(fn)`` over the leading pod dim of ``arrays``.

    On a mesh with a ``"pod"`` axis the map runs under ``shard_map``, so
    each device codes only the pods it holds: a Pallas kernel has no
    partitioning rule, and the SPMD partitioner refuses it rather than
    gather every pod's buffer onto every device."""
    from jax.sharding import PartitionSpec as P

    from repro.sharding.rules import current_mesh

    mesh = current_mesh()
    if mesh is None or dict(mesh.shape).get("pod", 1) == 1:
        return jax.vmap(fn)(*arrays)
    return jax.shard_map(jax.vmap(fn), mesh=mesh, in_specs=P("pod"),
                         out_specs=P("pod"), check_vma=False)(*arrays)


def _encode_bucket(cfg: SyncConfig, flat: jnp.ndarray, want_local: bool
                   ) -> Tuple[Tuple[ChunkPayload, ...],
                              Optional[jnp.ndarray]]:
    """Encode one bucket segment into wire chunks (+ local reconstruction).

    ``flat``: (n_pods, N_g).  One encode/decode pair is bound to this
    bucket's (block, tier) knobs — the per-bucket codec dispatch point.
    The permute of chunk i is data-independent of the encode of chunk i+1
    (``SyncConfig.overlap_chunks``): on a real mesh the transfer of one
    chunk hides behind the compression of the next, which is what
    ``MeshTransport.measure_overlap`` measures and the WAN simulator
    models."""
    from repro.kernels import ops as kops
    from repro.kernels.wan_codec import k_per_block

    n_total = flat.shape[1]
    block = min(cfg.codec_block, max(1, n_total))
    k_block = k_per_block(block, cfg.compress_topk)
    encode, decode = kops.wan_codec_fns(block=block,
                                        value_dtype=cfg.value_dtype)
    chunks, local_parts, off = [], [], 0
    for m in _chunk_widths(cfg, n_total):
        with jax.named_scope("sync_encode"):
            seg = flat[:, off:off + m]
            off += m
            q, idx, scales = _per_pod(lambda f: encode(f, k_block), seg)
            chunks.append(ChunkPayload(q=q, idx=idx.astype(jnp.uint16),
                                       scales=scales))
        if want_local:
            with jax.named_scope("sync_ef"):
                local_parts.append(_per_pod(
                    lambda a, i, s: decode(a, i, s, m), q, idx, scales))
    with jax.named_scope("sync_ef"):
        local = (jnp.concatenate(local_parts, axis=1) if want_local
                 else None)
    return tuple(chunks), local


def _decode_chunks(cfg: SyncConfig, chunks: Sequence[ChunkPayload],
                   widths: Sequence[int], n_total: int) -> jnp.ndarray:
    """Decode an explicit (chunk, width) list of one bucket's wire chunks.
    ``n_total`` is the width the bucket was *encoded* at — it fixes the
    codec block, so a chunk prefix decodes bit-identically whether or not
    the round shipped the rest of the bucket (chunks are independent)."""
    from repro.kernels import ops as kops

    block = min(cfg.codec_block, max(1, n_total))
    _, decode = kops.wan_codec_fns(block=block, value_dtype=cfg.value_dtype)
    parts = [_per_pod(lambda a, i, s: decode(a, i, s, m),
                      c.q, c.idx.astype(jnp.int32), c.scales)
        for c, m in zip(chunks, widths)]
    return jnp.concatenate(parts, axis=1)


def _decode_bucket(cfg: SyncConfig, chunks: Sequence[ChunkPayload],
                   n_total: int) -> jnp.ndarray:
    """Decode one bucket's (shipped) wire chunks back to dense."""
    return _decode_chunks(cfg, chunks, _chunk_widths(cfg, n_total), n_total)


class TransferFailed(RuntimeError):
    """One bucket's ring transfer failed (timeout, drop, link fault) —
    retryable: :func:`ship_sync_payloads` re-ships the bucket up to the
    transport's ``retry_policy.max_retries`` before declaring the peer
    unreachable."""

    def __init__(self, bucket: str, attempt: int, reason: str = "",
                 pod: Optional[int] = None):
        self.bucket, self.attempt = bucket, attempt
        self.reason, self.pod = reason, pod
        super().__init__(
            f"transfer of bucket {bucket!r} failed on attempt {attempt}"
            + (f": {reason}" if reason else ""))


class CorruptPayloadError(TransferFailed):
    """Shipped wire chunks failed checksum verification — retryable (a
    re-send re-reads the sender's intact buffer)."""


class PodUnreachableError(RuntimeError):
    """Retries exhausted (or a pod crashed mid-round): the peer missed the
    sync barrier.  The round either completes degraded over the surviving
    membership mask (``finish_codec_sync(..., alive=...)``) or rolls back
    to the last sync barrier checkpoint — the launcher decides."""

    def __init__(self, pod: Optional[int] = None,
                 step: Optional[int] = None, bucket: str = ""):
        self.pod, self.step, self.bucket = pod, step, bucket
        where = f"pod {pod}" if pod is not None else "peer"
        at = f" at step {step}" if step is not None else ""
        via = f" (bucket {bucket!r})" if bucket else ""
        super().__init__(f"{where} unreachable{at}{via}: retries exhausted")


def chunk_checksum_rows(chunks: Sequence[ChunkPayload]) -> Tuple[int, ...]:
    """Per-pod-row CRC32 over one bucket's wire chunks (q ‖ idx ‖ scales
    bytes, chunk by chunk) — the wire-format integrity word the
    fault-tolerant ship path verifies after a transfer.  Host-side: pulls
    device buffers, so it only runs on host-seam transports (never inside
    a jit trace)."""
    import zlib

    n_pods = int(chunks[0].q.shape[0])
    out = []
    for p in range(n_pods):
        crc = 0
        for c in chunks:
            for part in (c.q, c.idx, c.scales):
                crc = zlib.crc32(
                    np.ascontiguousarray(np.asarray(part[p])).tobytes(), crc)
        out.append(crc)
    return tuple(out)


def verify_shipment(name: str, sent_crc: Sequence[int],
                    shipped: Sequence[ChunkPayload], shift: int) -> None:
    """Check a shipped bucket against pre-ship checksums: under the ring
    permute, shipped row ``p`` must be sender row ``(p - shift) % n``
    bit-for-bit.  Raises :class:`CorruptPayloadError` naming the first
    mismatching receiver row."""
    n = len(sent_crc)
    got = chunk_checksum_rows(shipped)
    for p in range(n):
        if got[p] != sent_crc[(p - shift) % n]:
            raise CorruptPayloadError(
                name, 0, f"checksum mismatch on receiver row {p}", pod=p)


class InlineRingShip:
    """The default transport: ring-permute each wire part in place, traced
    into the enclosing jit (-> one collective-permute per part under SPMD).
    Real transports (:mod:`repro.core.transport`) implement the same
    ``ship_bucket`` contract; this degenerate one is why ``transport=None``
    is bit-identical to the pre-seam inline path."""

    in_graph = True

    def ship_bucket(self, name: str, chunks: Sequence[ChunkPayload],
                    shift: int, payload_mb: float = 0.0
                    ) -> Tuple[ChunkPayload, ...]:
        del name, payload_mb
        with jax.named_scope("sync_ring"):
            return tuple(ChunkPayload(*(jnp.roll(p, shift, axis=0)
                                        for p in c)) for c in chunks)


_INLINE_RING = InlineRingShip()


def bucket_wire_mb(cfg: SyncConfig, layout: BucketLayout
                   ) -> Dict[str, float]:
    """Per-pod wire megabytes per non-empty bucket group for one sync round
    (host-side, static) — what transports bill/record per transfer."""
    return {name: cfg.for_bucket(name).payload_mb(
        layout.sizes[g] * 4 / 1e6)
        for g, name in enumerate(layout.names) if layout.sizes[g]}


def prepare_codec_sync(cfg: SyncConfig, state: SyncState) -> SyncPayloads:
    """The codec round's *decide/pack* stage (jit-able): average the
    accumulated gradient, fold in the EF residual, pack the bucket-grouped
    buffer and encode every non-empty bucket segment at its own (top-k,
    tier, block) knobs.  What comes out is exactly what a transport ships —
    ``apply_sync`` composes this with a ship and :func:`finish_codec_sync`,
    and the trainer's host-seam path runs the three stages as separate
    dispatches so a real transport can time each bucket's transfer."""
    with jax.named_scope("sync_encode"):
        denom = jnp.maximum(state.steps_since_sync, 1).astype(jnp.float32)
        avg = jax.tree.map(lambda b: b / denom, state.ga_buffer)
        layout = bucket_layout(cfg, avg)
        flat = _pack_stacked(avg, layout)
        if cfg.error_feedback:
            flat = flat + state.ef_residual
    chunks: Dict[str, Tuple[ChunkPayload, ...]] = {}
    local_parts = []
    for g, name in enumerate(layout.names):
        off, size = layout.offsets[g], layout.sizes[g]
        if size == 0:
            continue
        bchunks, local = _encode_bucket(cfg.for_bucket(name),
                                        flat[:, off:off + size],
                                        want_local=cfg.error_feedback)
        chunks[name] = bchunks
        if cfg.error_feedback:
            local_parts.append(local)
    with jax.named_scope("sync_ef"):
        local = (jnp.concatenate(local_parts, axis=1) if local_parts
                 else (flat[:, :0] if cfg.error_feedback else None))
    return SyncPayloads(flat=flat, local=local, chunks=chunks)


def ship_sync_payloads(cfg: SyncConfig,
                       chunks: Mapping[str, Tuple[ChunkPayload, ...]],
                       transport=None,
                       wire_mb: Optional[Mapping[str, float]] = None
                       ) -> Dict[str, Tuple[ChunkPayload, ...]]:
    """Emit every bucket's wire chunks to the transport's one-peer ring
    send.  ``transport=None`` is the in-graph inline ring (bit-exact
    legacy path); a host-seam transport executes + times each bucket's
    transfer here.

    Fault tolerance rides the transport's optional attributes: a
    ``retry_policy`` (:class:`repro.core.wan.RetryPolicy`) bounds how many
    :class:`TransferFailed` raises per bucket are retried before
    :class:`PodUnreachableError`; ``verify_checksums`` (host-seam only)
    checksums each bucket pre-ship and verifies the shipped rows, so a
    corrupted payload is caught and re-shipped instead of decoded into
    the parameters.  Transports without these attributes get the original
    single-attempt path unchanged."""
    ship = transport if transport is not None else _INLINE_RING
    wire_mb = wire_mb or {}
    in_graph = getattr(ship, "in_graph", True)
    verify = bool(getattr(ship, "verify_checksums", False)) and not in_graph
    policy = getattr(ship, "retry_policy", None)
    max_retries = int(policy.max_retries) if policy is not None else 0
    note_retry = getattr(ship, "note_retry", None)
    out: Dict[str, Tuple[ChunkPayload, ...]] = {}
    for name, bchunks in chunks.items():
        sent_crc = chunk_checksum_rows(bchunks) if verify else None
        attempt = 0
        while True:
            try:
                shipped = ship.ship_bucket(name, bchunks, cfg.peer_shift,
                                           wire_mb.get(name, 0.0))
                if verify:
                    verify_shipment(name, sent_crc, shipped, cfg.peer_shift)
                break
            except TransferFailed as err:
                attempt += 1
                if attempt > max_retries:
                    raise PodUnreachableError(pod=err.pod,
                                              bucket=name) from err
                if note_retry is not None:
                    note_retry(name, attempt, err)
        out[name] = shipped
    return out


def finish_codec_sync(cfg: SyncConfig, params: Pytree, state: SyncState,
                      payloads: SyncPayloads,
                      shipped: Mapping[str, Tuple[ChunkPayload, ...]],
                      lr: Union[jnp.ndarray, float] = 1.0,
                      alive: Optional[jnp.ndarray] = None
                      ) -> Tuple[Pytree, SyncState]:
    """The codec round's tail (jit-able): decode the shipped chunks, apply
    the receiver-side SGD update, and roll the EF residual + per-bucket
    telemetry into the new :class:`SyncState`.

    ``alive`` (``(n_pods,)`` 1/0 mask, default all-alive) is the degraded
    round: a peer update is applied only where both the receiver and its
    ring sender are alive; a sender whose message never arrived (it died,
    or its receiver did) keeps the FULL message as its EF residual, so
    nothing sent into a dead link is lost — it redelivers next round, and
    a later pod shrink replay-accumulates it sum-preservingly
    (:func:`resize_sync_state`).  Undelivered rows' ``msg_norm`` /
    ``resid_norm`` zero out, which the adaptive controllers already read
    as "no reading yet" — a degraded round is evidence-free, never a
    spurious ef-guard trip."""
    layout = bucket_layout(cfg, state.ga_buffer)
    peer_parts = []
    with jax.named_scope("sync_apply"):
        for g, name in enumerate(layout.names):
            size = layout.sizes[g]
            if size == 0:
                peer_parts.append(payloads.flat[:, :0])
                continue
            peer_parts.append(_decode_bucket(cfg.for_bucket(name),
                                             shipped[name], size))
        peer_flat = jnp.concatenate(peer_parts, axis=1)
    return _finish_from_peer(cfg, params, state, payloads.flat,
                             payloads.local, peer_flat, layout, lr, alive)


def _finish_from_peer(cfg: SyncConfig, params: Pytree, state: SyncState,
                      flat: jnp.ndarray, local: Optional[jnp.ndarray],
                      peer_flat: jnp.ndarray, layout: BucketLayout,
                      lr: Union[jnp.ndarray, float],
                      alive: Optional[jnp.ndarray]
                      ) -> Tuple[Pytree, SyncState]:
    """Common tail of the codec round once the peer message is dense:
    alive masking, receiver SGD, EF rollover and telemetry.  ``local`` is
    the sender-side reconstruction of what the peer will decode — the
    full-round one on the plain path, the spliced prefix+tail one on the
    streaming retune path."""
    applied = delivered = None
    with jax.named_scope("sync_apply"):
        if alive is not None:
            alive = jnp.asarray(alive, jnp.float32)
            # receiver p applies iff p and its ring sender (p - shift) are alive
            applied = alive * jnp.roll(alive, cfg.peer_shift)
            # sender p's message arrived iff p and its receiver (p + shift) are
            delivered = alive * jnp.roll(alive, -cfg.peer_shift)
            peer_flat = peer_flat * applied[:, None]
        peer = _unpack_stacked(peer_flat, state.ga_buffer, layout)
        # per-pod, per-bucket message norms — with EF also the residual
        # norms; their ratio is the convergence signal the adaptive
        # controllers guard on (a bucket's residual growing toward its
        # message norm means that bucket's tier is dropping more than EF
        # can recover per interval)
        msg_norm = _bucket_norms(flat, layout)
    new_resid, resid_norm = state.ef_residual, state.resid_norm
    if cfg.error_feedback:
        with jax.named_scope("sync_ef"):
            new_resid = flat - local
            if delivered is not None:
                new_resid = jnp.where(delivered[:, None] > 0, new_resid,
                                      flat)
            resid_norm = _bucket_norms(new_resid, layout)
    with jax.named_scope("sync_apply"):
        if delivered is not None:
            msg_norm = msg_norm * delivered[:, None]
            resid_norm = resid_norm * delivered[:, None]
        scale = jnp.asarray(lr, jnp.float32) * cfg.ga_lr_scale
        params = jax.tree.map(
            lambda p, g: (p.astype(jnp.float32) - scale * g).astype(p.dtype),
            params, peer)
        buf = jax.tree.map(jnp.zeros_like, state.ga_buffer)
        tier = jnp.asarray(cfg.bucket_tiers, jnp.int32)
    zero = state._replace(steps_since_sync=jnp.zeros((), jnp.int32))
    return params, zero._replace(ga_buffer=buf, ef_residual=new_resid,
                                 tier=tier, msg_norm=msg_norm,
                                 resid_norm=resid_norm)


# ----------------------------------------------- streaming mid-round retune


def reencode_unsent(cfg: SyncConfig, cfg_to: SyncConfig, flat: jnp.ndarray,
                    layout: BucketLayout, sent: Mapping[str, int]
                    ) -> Tuple[Dict[str, Tuple[ChunkPayload, ...]],
                               Dict[str, jnp.ndarray]]:
    """Re-encode every bucket's *unsent* chunk tail at ``cfg_to``'s
    cheaper (topk, dtype) knobs — the streaming mid-round retune.

    ``sent`` maps bucket name -> number of ``cfg``-schedule chunks already
    shipped (buckets absent default to fully shipped).  Chunks split on
    codec-block boundaries and ``cfg_to`` carries ``cfg``'s ``codec_block``
    (the ladder only moves topk/dtype), so the sent prefix keeps its exact
    encoding and the tail re-encodes standalone: block-local selection
    never looks across the cut.  Returns ``(tail_chunks, tail_local)``
    keyed by bucket (only buckets with an unsent tail appear); the caller
    splices them into the round with :func:`finish_codec_sync_split`,
    whose EF rollover then *exactly* carries the tail's fidelity delta —
    the convergence guards' contract survives the retune."""
    tails: Dict[str, Tuple[ChunkPayload, ...]] = {}
    locals_: Dict[str, jnp.ndarray] = {}
    for g, name in enumerate(layout.names):
        off, size = layout.offsets[g], layout.sizes[g]
        if size == 0:
            continue
        widths = _chunk_widths(cfg.for_bucket(name), size)
        n_sent = sent.get(name, len(widths))
        sw = int(sum(widths[:n_sent]))
        if sw >= size:
            continue
        tchunks, tlocal = _encode_bucket(cfg_to.for_bucket(name),
                                         flat[:, off + sw:off + size],
                                         want_local=cfg.error_feedback)
        tails[name] = tchunks
        locals_[name] = tlocal
    return tails, locals_


def finish_codec_sync_split(cfg: SyncConfig, cfg_to: SyncConfig,
                            params: Pytree, state: SyncState,
                            payloads: SyncPayloads,
                            shipped: Mapping[str, Tuple[ChunkPayload, ...]],
                            tail_shipped: Mapping[str,
                                                  Tuple[ChunkPayload, ...]],
                            tail_local: Mapping[str, jnp.ndarray],
                            sent: Mapping[str, int],
                            lr: Union[jnp.ndarray, float] = 1.0,
                            alive: Optional[jnp.ndarray] = None
                            ) -> Tuple[Pytree, SyncState]:
    """Finish a streaming round that retuned mid-round: each bucket's
    peer message is the shipped ``cfg`` prefix chunks plus the shipped
    ``cfg_to`` tail chunks, and the sender-side reconstruction is spliced
    the same way — so ``ef_residual = flat - spliced_local`` carries
    exactly the fidelity the cheaper tail dropped.  The persistent config
    (and ``SyncState.tier`` telemetry) stays ``cfg``'s: the retune is
    transient, owned by this round alone."""
    layout = bucket_layout(cfg, state.ga_buffer)
    peer_parts, local_parts = [], []
    for g, name in enumerate(layout.names):
        off, size = layout.offsets[g], layout.sizes[g]
        if size == 0:
            peer_parts.append(payloads.flat[:, :0])
            continue
        bcfg = cfg.for_bucket(name)
        widths = _chunk_widths(bcfg, size)
        n_sent = sent.get(name, len(widths))
        sw = int(sum(widths[:n_sent]))
        parts, lparts = [], []
        if n_sent:
            parts.append(_decode_chunks(bcfg, shipped[name][:n_sent],
                                        widths[:n_sent], size))
            if cfg.error_feedback:
                lparts.append(payloads.local[:, off:off + sw])
        if sw < size:
            parts.append(_decode_bucket(cfg_to.for_bucket(name),
                                        tail_shipped[name], size - sw))
            if cfg.error_feedback:
                lparts.append(tail_local[name])
        peer_parts.append(parts[0] if len(parts) == 1
                          else jnp.concatenate(parts, axis=1))
        if cfg.error_feedback:
            local_parts.append(lparts[0] if len(lparts) == 1
                               else jnp.concatenate(lparts, axis=1))
    peer_flat = jnp.concatenate(peer_parts, axis=1)
    local = (jnp.concatenate(local_parts, axis=1) if local_parts
             else (payloads.flat[:, :0] if cfg.error_feedback else None))
    return _finish_from_peer(cfg, params, state, payloads.flat, local,
                             peer_flat, layout, lr, alive)


def bucket_chunk_mb(cfg: SyncConfig, layout: BucketLayout
                    ) -> Dict[str, Tuple[float, ...]]:
    """Per-chunk wire megabytes of each non-empty bucket (host-side,
    static) — the streaming ship's chunk schedule, summing to the bucket's
    :func:`bucket_wire_mb` entry up to float association."""
    out: Dict[str, Tuple[float, ...]] = {}
    for g, name in enumerate(layout.names):
        size = layout.sizes[g]
        if size == 0:
            continue
        bcfg = cfg.for_bucket(name)
        out[name] = tuple(bcfg.payload_mb(m * 4 / 1e6)
                          for m in _chunk_widths(bcfg, size))
    return out


def _bucket_norms(flat: jnp.ndarray, layout: BucketLayout) -> jnp.ndarray:
    """Per-pod, per-bucket L2 norms of a bucket-grouped buffer:
    (n_pods, n_buckets), zero columns for empty groups."""
    cols = [jnp.linalg.norm(flat[:, off:off + size], axis=1)
            if size else jnp.zeros((flat.shape[0],), jnp.float32)
            for off, size in zip(layout.offsets, layout.sizes)]
    return jnp.stack(cols, axis=1)


def _ship_ring(cfg: SyncConfig, tree: Pytree) -> Pytree:
    """One-peer ring send: roll along the pod dim (-> collective-permute)."""
    if 0.0 < cfg.compress_topk < 1.0:
        from repro.kernels import ops as kops

        # keep per-selection index spaces below int32 (trillion-param
        # accumulated-gradient leaves overflow a flat index otherwise)
        CHUNK = 1 << 26

        def ship(x):
            n_pods = x.shape[0]
            numel = int(np_prod(x.shape[1:]))
            pad = (-numel) % min(CHUNK, numel)
            chunk = min(CHUNK, numel)
            flat = x.reshape(n_pods, -1)
            if pad:
                flat = jnp.pad(flat, ((0, 0), (0, pad)))
            nch = flat.shape[1] // chunk
            k = max(1, int(chunk * cfg.compress_topk))
            f3 = flat.reshape(n_pods, nch, chunk)
            with jax.named_scope("sync_encode"):
                vals, idx = jax.vmap(jax.vmap(
                    lambda f: kops.topk_compress(f, k)))(f3)
            with jax.named_scope("sync_ring"):
                vals = jnp.roll(vals, cfg.peer_shift, axis=0)
                idx = jnp.roll(idx, cfg.peer_shift, axis=0)
            with jax.named_scope("sync_apply"):
                dense = jax.vmap(jax.vmap(
                    lambda v, i: kops.topk_decompress(v, i, chunk)))(vals,
                                                                     idx)
            dense = dense.reshape(n_pods, -1)
            if pad:
                dense = dense[:, :numel]
            return dense.reshape(x.shape)

        return jax.tree.map(ship, tree)
    with jax.named_scope("sync_ring"):
        return jax.tree.map(lambda x: jnp.roll(x, cfg.peer_shift, axis=0),
                            tree)


def apply_sync(cfg: SyncConfig, params: Pytree, state: SyncState,
               lr: Union[jnp.ndarray, float] = 1.0, transport=None
               ) -> Tuple[Pytree, SyncState]:
    """One inter-pod synchronization round (paper §III.C steps 3-5).

    ``params`` leaves have the leading pod dim.  ``lr`` drives the
    receiver-side SGD update of ASGD-GA.  On the codec path the round is
    three stages — :func:`prepare_codec_sync` (decide/pack/encode),
    :func:`ship_sync_payloads` (the transport seam), and
    :func:`finish_codec_sync` (decode/update/EF) — and ``transport``
    selects who ships: ``None`` means the in-graph inline ring (bit-exact
    legacy behaviour, traceable); a host-seam transport
    (:class:`~repro.core.transport.MeshTransport`) executes and times each
    bucket's transfer, in which case this function must run OUTSIDE jit
    (the trainer's split path jits the prepare/finish stages separately).
    """
    n_pods = jax.tree.leaves(params)[0].shape[0]
    zero = state._replace(steps_since_sync=jnp.zeros((), jnp.int32))
    if n_pods <= 1 or cfg.strategy == "asgd":
        return params, zero

    if cfg.strategy == "asgd_ga":
        if cfg.uses_codec:
            # fused codec: bucket -> (+ EF residual) -> per-bucket top-k ->
            # quantize -> ship -> decode; the residual keeps everything the
            # codec dropped for re-injection at the next sync (EF-SGD)
            payloads = prepare_codec_sync(cfg, state)
            wire = bucket_wire_mb(cfg, bucket_layout(cfg, state.ga_buffer))
            shipped = ship_sync_payloads(cfg, payloads.chunks, transport,
                                         wire)
            return finish_codec_sync(cfg, params, state, payloads, shipped,
                                     lr)
        with jax.named_scope("sync_encode"):
            denom = jnp.maximum(state.steps_since_sync,
                                1).astype(jnp.float32)
            avg = jax.tree.map(lambda b: b / denom, state.ga_buffer)
        peer = _ship_ring(cfg, avg)
        with jax.named_scope("sync_apply"):
            scale = jnp.asarray(lr, jnp.float32) * cfg.ga_lr_scale
            params = jax.tree.map(
                lambda p, g: (p.astype(jnp.float32)
                              - scale * g).astype(p.dtype),
                params, peer)
            buf = jax.tree.map(jnp.zeros_like, state.ga_buffer)
            tier = jnp.asarray(cfg.bucket_tiers, jnp.int32)
        return params, zero._replace(ga_buffer=buf, tier=tier)

    if cfg.strategy == "asp":
        # Gaia-style Approximate Synchronous Parallel: ship only parameter
        # deltas whose relative magnitude since the last sync exceeds the
        # significance threshold (the paper's main comparison system,
        # implemented as a baseline).  Insignificant deltas keep accumulating
        # in place (params themselves carry them).
        eps = 1e-8
        ref = state.ga_buffer
        with jax.named_scope("sync_encode"):
            delta = jax.tree.map(
                lambda p, r: p.astype(jnp.float32) - r, params, ref)
            sig = jax.tree.map(
                lambda d, r: jnp.abs(d) > cfg.asp_threshold * (jnp.abs(r)
                                                              + eps),
                delta, ref)
            shipped = jax.tree.map(
                lambda d, m: jnp.where(m, d, 0.0), delta, sig)
            n_sig = sum(jnp.sum(m) for m in jax.tree.leaves(sig))
            n_tot = sum(m.size for m in jax.tree.leaves(sig))
            frac = n_sig.astype(jnp.float32) / n_tot
        peer = _ship_ring(cfg, shipped)
        with jax.named_scope("sync_apply"):
            params = jax.tree.map(
                lambda p, q: (p.astype(jnp.float32)
                              + 0.5 * q).astype(p.dtype),
                params, peer)
            new_ref = jax.tree.map(lambda p: p.astype(jnp.float32), params)
        return params, zero._replace(ga_buffer=new_ref,
                                     significant_frac=frac)

    if cfg.strategy == "ama":
        peer = _ship_ring(cfg, params)
        with jax.named_scope("sync_apply"):
            params = jax.tree.map(
                lambda p, q: ((p.astype(jnp.float32)
                               + q.astype(jnp.float32)) * 0.5
                              ).astype(p.dtype),
                params, peer)
        return params, zero

    # sma — barrier global average
    with jax.named_scope("sync_apply"):
        params = jax.tree.map(
            lambda p: jnp.broadcast_to(
                jnp.mean(p.astype(jnp.float32), axis=0, keepdims=True),
                p.shape).astype(p.dtype),
            params)
    return params, zero


def hierarchical_average(tree: Pytree, groups: Sequence[Sequence[int]],
                         inter: str = "ama", shift: int = 1) -> Pytree:
    """Two-level averaging: the existing strategies mapped onto hierarchy
    levels (paper §III.C's inter-PS model averaging across regions).

    ``groups`` partitions the pod axis into regions.  The intra level is a
    barrier mean within each region (``sma`` semantics over the region's
    fast fabric); the inter level exchanges the *region means*: ``ama``
    gossips them one ring step (MA between region parameter servers),
    ``sma`` takes their global mean.  The result is broadcast back to
    every member.

    Degenerate shapes recover the flat strategies exactly (property-tested
    in ``tests/test_topology.py``): all-singleton groups in pod order with
    ``inter="ama"`` reproduce flat ``ama`` bit-for-bit (a size-one mean is
    the identity, and the region ring is then the pod ring), and a single
    group reproduces flat ``sma`` (the inter level collapses to the
    identity on the one region mean)."""
    groups = tuple(tuple(int(i) for i in g) for g in groups)
    if not groups or any(not g for g in groups):
        raise ValueError("groups must be non-empty and cover every pod")
    members = [i for g in groups for i in g]
    leaves = jax.tree.leaves(tree)
    if not leaves:
        return tree
    n_pods = leaves[0].shape[0]
    if sorted(members) != list(range(n_pods)):
        raise ValueError(f"groups {groups} do not partition pods "
                         f"0..{n_pods - 1}")
    n_groups = len(groups)
    if inter not in ("ama", "sma"):
        raise ValueError(f"inter level must be 'ama' or 'sma', got {inter!r}")
    if inter == "ama" and n_groups > 1 and math.gcd(shift, n_groups) != 1:
        raise ValueError(f"inter-ring shift {shift} must be coprime with "
                         f"the number of regions {n_groups}")
    # pod i receives the aggregate of the group it belongs to
    assign = np.empty(n_pods, dtype=np.int32)
    for gi, g in enumerate(groups):
        assign[list(g)] = gi
    assign = jnp.asarray(assign)
    gathers = [jnp.asarray(g, dtype=jnp.int32) for g in groups]

    def avg(p):
        x = p.astype(jnp.float32)
        m = jnp.stack([jnp.mean(x[idx], axis=0) for idx in gathers])
        if inter == "ama":
            m = (m + jnp.roll(m, shift, axis=0)) * 0.5
        else:
            m = jnp.broadcast_to(jnp.mean(m, axis=0, keepdims=True), m.shape)
        return m[assign].astype(p.dtype)

    return jax.tree.map(avg, tree)


# ---------------------------------------------------------------------------
# pod-count-changing state transforms (elasticity engine)
# ---------------------------------------------------------------------------
#
# A reconfiguration (cloud joined / left) changes ``n_pods`` mid-run.  Under
# the stacked representation that is a resize of every leaf's leading pod
# dimension, applied at a sync barrier.  Two families:
#
# - parameter-like leaves ("mean" semantics): the global parameter mean must
#   be preserved — new pods are seeded with the mean replica on grow, and on
#   shrink the survivors are shifted so their mean equals the old global mean
#   (removed pods' progress is re-averaged in, not discarded).
# - accumulator-like leaves ("sum" semantics, the ASGD-GA gradient buffer):
#   the *total* accumulated gradient must be preserved — new pods start at
#   zero on grow, and on shrink the removed pods' accumulations are
#   replay-distributed evenly across the survivors.


def grow_pods(tree: Pytree, n_new: int, how: str = "mean") -> Pytree:
    """Grow the leading pod dimension to ``n_new`` (>= current).

    ``how``: "mean" appends mean-of-existing replicas (preserves the global
    parameter mean), "clone" appends copies of pod 0, "zeros" appends zero
    pods (preserves accumulator totals).
    """
    leaves = jax.tree.leaves(tree)
    if not leaves:
        return tree   # stateless (e.g. plain-SGD optimizer state)
    n_old = leaves[0].shape[0]
    if n_new < n_old:
        raise ValueError(f"grow_pods: {n_new} < current {n_old}")
    if n_new == n_old:
        return tree
    k = n_new - n_old

    def grow(x):
        if x.ndim == 0 or x.shape[0] != n_old:
            return x   # scalar bookkeeping leaf, no pod dim
        if how == "mean":
            fill = jnp.broadcast_to(
                jnp.mean(x.astype(jnp.float32), axis=0, keepdims=True),
                (k,) + x.shape[1:]).astype(x.dtype)
        elif how == "clone":
            fill = jnp.broadcast_to(x[:1], (k,) + x.shape[1:])
        elif how == "zeros":
            fill = jnp.zeros((k,) + x.shape[1:], x.dtype)
        else:
            raise ValueError(f"grow_pods: unknown how={how!r}")
        return jnp.concatenate([x, fill], axis=0)

    return jax.tree.map(grow, tree)


def shrink_pods(tree: Pytree, keep: Sequence[int], how: str = "mean") -> Pytree:
    """Shrink the leading pod dimension to the pods in ``keep`` (ordered).

    ``how``: "mean" shifts survivors so their mean equals the old global mean
    (re-averaging the departed pods' progress in), "sum" redistributes the
    removed pods' values evenly over survivors (preserves the total —
    replay-accumulate for gradient buffers), "drop" discards removed pods.
    """
    keep = tuple(int(i) for i in keep)
    if not keep:
        raise ValueError("shrink_pods: keep must be non-empty")
    leaves = jax.tree.leaves(tree)
    if not leaves:
        return tree   # stateless (e.g. plain-SGD optimizer state)
    n_old = leaves[0].shape[0]
    if any(i < 0 or i >= n_old for i in keep):
        raise ValueError(f"shrink_pods: keep {keep} out of range for {n_old}")
    if len(set(keep)) != len(keep):
        raise ValueError("shrink_pods: duplicate indices in keep")
    removed = tuple(i for i in range(n_old) if i not in keep)
    idx = jnp.asarray(keep)

    def shrink(x):
        if x.ndim == 0 or x.shape[0] != n_old:
            return x
        kept = jnp.take(x, idx, axis=0)
        if how == "drop" or not removed:
            return kept
        xf = x.astype(jnp.float32)
        kf = kept.astype(jnp.float32)
        if how == "mean":
            shift = (jnp.mean(xf, axis=0, keepdims=True)
                     - jnp.mean(kf, axis=0, keepdims=True))
            return (kf + shift).astype(x.dtype)
        if how == "sum":
            lost = jnp.sum(jnp.take(xf, jnp.asarray(removed), axis=0),
                           axis=0, keepdims=True)
            return (kf + lost / len(keep)).astype(x.dtype)
        raise ValueError(f"shrink_pods: unknown how={how!r}")

    return jax.tree.map(shrink, tree)


def resize_sync_state(cfg: SyncConfig, state: SyncState, new_params: Pytree,
                      keep: Optional[Sequence[int]] = None) -> SyncState:
    """Carry ``SyncState`` across a pod-count change.

    ``new_params`` are the already-resized stacked parameters.  Strategy
    semantics: ASGD-GA replay-accumulates the departed pods' gradient buffer
    into the survivors (sum-preserving) and zero-seeds joiners; ASP resets
    its reference to the new parameters (deltas restart from the
    reconfigured model); the bufferless strategies just re-init.
    """
    n_new = jax.tree.leaves(new_params)[0].shape[0]
    if cfg.strategy == "asgd_ga":
        buf = state.ga_buffer
        n_old = jax.tree.leaves(buf)[0].shape[0] if jax.tree.leaves(buf) else 0
        # the EF residual is accumulator-like (sum semantics): departed
        # pods' un-retransmitted error is replay-distributed, joiners start
        # with none
        resid = state.ef_residual
        if keep is not None and len(keep) < n_old:
            buf = shrink_pods(buf, keep, how="sum")
            resid = shrink_pods([resid], keep, how="sum")[0]
            n_old = len(keep)
        if n_new > n_old:
            buf = grow_pods(buf, n_new, how="zeros")
            resid = grow_pods([resid], n_new, how="zeros")[0]
        # msg/resid norms are transient telemetry of the *last* sync round:
        # a pod-count change invalidates them, so they re-arm at zero (the
        # adaptive controllers treat zeros as "no reading yet"); the active
        # per-bucket tiers survive the resize untouched, and the bucket
        # partition itself is pod-count-independent (it is a property of
        # the per-pod leaf shapes), so the grouped EF-residual segments
        # stay aligned through the pod-axis grow/shrink above
        nb = len(cfg.bucket_names)
        return state._replace(
            ga_buffer=buf, ef_residual=resid,
            msg_norm=jnp.zeros((n_new, nb), jnp.float32),
            resid_norm=jnp.zeros((n_new, nb), jnp.float32))
    fresh = init_sync_state(cfg, new_params)
    return fresh._replace(steps_since_sync=state.steps_since_sync,
                          significant_frac=state.significant_frac,
                          tier=state.tier)


def retune_sync_state(new_cfg: SyncConfig, old_cfg: SyncConfig,
                      state: SyncState, stacked_params: Pytree) -> SyncState:
    """Carry ``SyncState`` across a *codec retune* (same strategy and pod
    count, different tier / top-k / interval — the adaptive controller's
    reconfiguration path).

    The EF residual is the one buffer whose meaning survives a tier change:
    it is defined in dense bucket coordinates (message minus what the peer
    reconstructed), independent of how the next message will be encoded —
    re-injecting it under the new tier is exactly EF-SGD semantics, and
    each bucket group's segment carries over *independently* (a retune
    that moves only the MoE bucket's tier leaves every other bucket's
    residual bytes untouched).  When the retune changes the bucket
    *policy* (single <-> layer-class) the grouped buffer order changes,
    so the residual is re-permuted leaf-chunk by leaf-chunk into the new
    layout — no residual mass is dropped.  It is dropped only when the
    new config stops tracking it (EF off) and zero-seeded when EF turns
    on.
    """
    if new_cfg.strategy != old_cfg.strategy:
        raise ValueError(
            f"retune cannot change strategy ({old_cfg.strategy!r} -> "
            f"{new_cfg.strategy!r}); that is a reconfiguration "
            f"(resize_sync_state / Trainer.reconfigure)")
    n_pods = jax.tree.leaves(stacked_params)[0].shape[0]
    want_ef = new_cfg.uses_codec and new_cfg.error_feedback
    had_ef = state.ef_residual.shape[1] > 0
    if want_ef and not had_ef:
        n = sum(x.size for x in jax.tree.leaves(stacked_params)) // n_pods
        resid = jnp.zeros((n_pods, n), jnp.float32)
    elif not want_ef:
        resid = jnp.zeros((n_pods, 0), jnp.float32)
    else:
        resid = state.ef_residual
        old_layout = bucket_layout(old_cfg, stacked_params)
        new_layout = bucket_layout(new_cfg, stacked_params)
        if old_layout.order != new_layout.order:
            # policy change re-groups the buffer: move each leaf's chunk
            # from its old offset to its new packing position
            old_off = old_layout.leaf_offsets
            resid = jnp.concatenate(
                [resid[:, old_off[i]:old_off[i] + old_layout.leaf_sizes[i]]
                 for i in new_layout.order], axis=1)
    nb_new, nb_old = len(new_cfg.bucket_names), len(old_cfg.bucket_names)
    msg_norm, resid_norm = state.msg_norm, state.resid_norm
    if nb_new != nb_old:
        # telemetry columns are per-bucket: a policy change re-arms them
        # at zero ("no reading yet") rather than mislabeling old readings
        msg_norm = jnp.zeros((n_pods, nb_new), jnp.float32)
        resid_norm = jnp.zeros((n_pods, nb_new), jnp.float32)
    return state._replace(ef_residual=resid,
                          tier=jnp.asarray(new_cfg.bucket_tiers, jnp.int32),
                          msg_norm=msg_norm, resid_norm=resid_norm)


# ---------------------------------------------------------------------------
# host-side schedule + traffic model
# ---------------------------------------------------------------------------


def is_sync_step(cfg: SyncConfig, step: int) -> bool:
    """Host-loop predicate: run ``apply_sync`` after this step?"""
    if cfg.strategy == "asgd":
        return False   # folded into every step's gradient reduction
    return (step + 1) % cfg.interval == 0


def traffic_per_step_mb(cfg: SyncConfig, model_mb: float,
                        bucket_weights: Optional[Mapping[str, float]] = None
                        ) -> float:
    """Average inter-pod WAN traffic per training step per pod.

    ``bucket_weights`` (from :func:`bucket_weights_of`) makes a
    layer-class config's accounting exact — each bucket group is billed
    at its own tier."""
    if cfg.strategy == "asgd":
        return model_mb
    return cfg.payload_mb(model_mb, bucket_weights=bucket_weights) \
        / cfg.interval


def migration_wire_mb(stacked_params: Pytree, n_new: int) -> float:
    """WAN bytes a *live* pod migration stages in the background.

    Each joining pod pulls one full fp32 per-pod replica from the last
    durable snapshot; each leaving pod pushes one replica-sized payload
    (its parameters + accumulator state folds into the survivors'
    sum-preserving resize).  Surviving pods move nothing — their state
    never leaves the device.  This traffic overlaps with training (the
    engine streams it off the step path), so the DES bills it as
    background ``traffic_mb``, not as pause; the only stall left is the
    one barrier-aligned reconcile."""
    n_old = jax.tree.leaves(stacked_params)[0].shape[0]
    per_pod_mb = sum(
        x.size * 4 for x in jax.tree.leaves(stacked_params)) / n_old / 1e6
    return per_pod_mb * abs(n_new - n_old)
