"""Typed spec layer: ONE config surface from build -> persist -> serve.

The paper sells token pooling as "a simple drop-in during indexation";
after four PRs the drop-in's knobs were threaded through five
uncoordinated surfaces (``ColbertConfig`` fields, ``Indexer(**index_kw)``,
the ``PARAM_KEYS`` tuple shadowed between ``core/index.py`` and
``core/persist.py``, a dozen hand-maintained argparse flags, and
``ServingEngine`` kwargs). This module is the single source of truth
they all derive from:

  * :class:`PoolingSpec`  — pooling method + factor, resolved through a
    REGISTRY of pooling strategies, so a new policy (e.g. per-document
    adaptive vector budgets, cf. "Efficient Constant-Space Multi-Vector
    Retrieval") is one ``register_pooling_strategy`` call, not an
    indexer fork.
  * :class:`IndexSpec`    — backend + construction knobs. Its
    :data:`INDEX_PARAM_KEYS` is THE definition the index, the sharded
    wrapper, and the persistence manifest all import (drift between
    shadowed copies silently rejected valid manifests).
  * :class:`ShardSpec`    — streaming-build / sharding knobs.
  * :class:`ServeSpec`    — batcher / shape-bucket / hot-swap knobs;
    ``launch/serve.py`` and ``benchmarks/serve_bench.py`` derive their
    argparse flags from it (:func:`add_spec_args`) instead of
    hand-maintaining them.
  * :class:`RetrieverSpec` — the composite the :class:`repro.Retriever`
    facade builds, persists, and serves from.

Specs are frozen dataclasses of JSON scalars: hashable, comparable by
value, and round-trip LOSSLESSLY through artifact manifests —
``retriever_spec_from_manifest(read_manifest(dir))`` reloads the exact
spec the index was built with in a fresh process
(tests/test_spec.py pins the property with hypothesis).

Backends live in a registry too (:func:`register_backend`): "cascade"
(retrieval/cascade.py) is a peer of flat/hnsw/plaid here, so every
artifact kind builds and serves through the same facade.

This module imports no index/persist/model code at module level — it is
the layer everything else depends on.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

# ---------------------------------------------------------------------------
# Single source of truth for index construction keys
# ---------------------------------------------------------------------------
# MultiVectorIndex construction knobs: what the persistence manifest
# records under "params", what ShardedIndex forwards to every shard, and
# what ``IndexSpec.params()`` emits. core/index.py and core/persist.py
# IMPORT this tuple (they used to shadow their own copies).
INDEX_PARAM_KEYS: Tuple[str, ...] = (
    "doc_maxlen", "n_centroids", "quant_bits", "nprobe",
    "t_cs", "ndocs", "hnsw_m", "hnsw_ef_construction",
    "hnsw_candidates")

# CascadeIndex construction knobs (its manifest records them top-level).
CASCADE_PARAM_KEYS: Tuple[str, ...] = (
    "coarse_factor", "fine_factor", "candidates", "doc_maxlen")


# ---------------------------------------------------------------------------
# Pooling strategy registry
# ---------------------------------------------------------------------------
# A pooling strategy maps per-document token embeddings to pooled slots:
#
#     strategy(x, mask, factor) -> (pooled, pooled_mask)
#
#       x:      [B, N, d] float token embeddings
#       mask:   [B, N]    bool — True where a real (emitted) token lives
#       factor: int >= 1  — the requested compression factor
#       pooled: [B, M, d] pooled vectors scattered into slots
#       pooled_mask: [B, M] bool — which slots hold a pooled vector
#
# ``compact_pooled`` (core/pooling.py) consumes the pair, so a strategy
# is free to choose M and the per-document vector budget — a per-doc
# adaptive-budget policy plugs in here without touching the indexer.
PoolingStrategy = Callable[..., Tuple[Any, Any]]

# The paper's methods, implemented by core/pooling.pool_doc_embeddings.
BUILTIN_POOL_METHODS: Tuple[str, ...] = ("none", "sequential", "kmeans",
                                         "ward")

_POOLING_REGISTRY: Dict[str, PoolingStrategy] = {}


def register_pooling_strategy(name: str, strategy: PoolingStrategy,
                              overwrite: bool = False) -> None:
    """Register a pooling policy under ``name`` so ``PoolingSpec(method=
    name)`` resolves to it everywhere (Indexer, Retriever, serve CLI)."""
    if not name or not isinstance(name, str):
        raise ValueError(f"strategy name must be a non-empty str, "
                         f"got {name!r}")
    if not overwrite and (name in BUILTIN_POOL_METHODS
                          or name in _POOLING_REGISTRY):
        raise ValueError(f"pooling strategy {name!r} already registered "
                         f"(pass overwrite=True to replace it)")
    _POOLING_REGISTRY[name] = strategy


def _builtin_strategy(method: str) -> PoolingStrategy:
    def run(x, mask, factor: int):
        from repro.core.pooling import pool_doc_embeddings
        return pool_doc_embeddings(x, mask, factor, method)
    return run


def pooling_strategy(name: str) -> PoolingStrategy:
    """Resolve a method name: registered strategies shadow builtins."""
    if name in _POOLING_REGISTRY:
        return _POOLING_REGISTRY[name]
    if name in BUILTIN_POOL_METHODS:
        return _builtin_strategy(name)
    raise KeyError(f"unknown pooling method {name!r}; known: "
                   f"{pooling_methods()}")


def pooling_methods() -> Tuple[str, ...]:
    """Builtins + registered strategies (the CLI's --pool-method choices)."""
    return BUILTIN_POOL_METHODS + tuple(
        n for n in _POOLING_REGISTRY if n not in BUILTIN_POOL_METHODS)


# ---------------------------------------------------------------------------
# Backend registry
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class BackendInfo:
    """One retrieval backend the facade can build / persist / serve."""
    name: str
    artifact_kind: str              # manifest "kind" this backend persists as
    param_keys: Tuple[str, ...]     # IndexSpec fields that apply to it
    # facade-level builder: (params, cfg, docs, spec, out_dir,
    # encode_batch) ->
    # (index, IndexStats). Filled by repro.api at import; a new backend
    # registers its own and rides Retriever/serve unchanged.
    builder: Optional[Callable] = None


_BACKEND_REGISTRY: Dict[str, BackendInfo] = {}


def register_backend(name: str, artifact_kind: str,
                     param_keys: Sequence[str],
                     builder: Optional[Callable] = None,
                     overwrite: bool = False) -> None:
    if not overwrite and name in _BACKEND_REGISTRY:
        raise ValueError(f"backend {name!r} already registered")
    _BACKEND_REGISTRY[name] = BackendInfo(
        name=name, artifact_kind=artifact_kind,
        param_keys=tuple(param_keys), builder=builder)


def backend_info(name: str) -> BackendInfo:
    if name not in _BACKEND_REGISTRY:
        raise KeyError(f"unknown backend {name!r}; known: "
                       f"{backend_names()}")
    return _BACKEND_REGISTRY[name]


def backend_names() -> Tuple[str, ...]:
    return tuple(_BACKEND_REGISTRY)


for _b in ("flat", "hnsw", "plaid"):
    register_backend(_b, "multi_vector_index", INDEX_PARAM_KEYS)
register_backend("cascade", "cascade_index", CASCADE_PARAM_KEYS)


# ---------------------------------------------------------------------------
# Spec base machinery
# ---------------------------------------------------------------------------
def _from_dict(cls, d: Dict[str, Any]):
    """Strict constructor: unknown keys are REJECTED (a typo'd knob must
    fail loudly, not silently fall back to a default)."""
    if not isinstance(d, dict):
        raise ValueError(f"{cls.__name__} expects a dict, got {type(d)}")
    names = {f.name for f in dataclasses.fields(cls)}
    unknown = set(d) - names
    if unknown:
        raise ValueError(f"unknown {cls.__name__} keys {sorted(unknown)}; "
                         f"known: {sorted(names)}")
    return cls(**d)


class _SpecBase:
    """Shared serialization for the frozen spec dataclasses."""

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]):
        return _from_dict(cls, d)

    def replace(self, **kw):
        """Frozen-friendly update; unknown keys raise (TypeError)."""
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# The specs
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class PoolingSpec(_SpecBase):
    """The paper's drop-in: WHICH pooling policy, at WHAT factor.

    ``factor <= 1`` is the identity (the unpooled baseline) regardless
    of ``method`` — exactly the pre-spec ``Indexer`` semantics, so
    pooled artifacts stay bit-identical across the redesign.
    """
    method: str = field(default="ward", metadata={
        "help": "token pooling method", "choices": pooling_methods})
    factor: int = field(default=1, metadata={
        "help": "pooling factor (1 = unpooled baseline)"})
    # Ward implementation toggle (kernels/ward_pool): "auto" resolves to
    # the Pallas kernel on TPU and to core/ward.py elsewhere (the two are
    # bitwise-equal); "kernel"/"ref" pin one (A/B parity gates,
    # debugging). Only meaningful for
    # method="ward"; carried but inert otherwise. RUNTIME-ONLY: never
    # persisted into manifests — both impls produce identical artifacts
    # (the bench gates it), so pinning an impl into an artifact would
    # only freeze a load-time execution choice that isn't content.
    ward_kernel: str = field(default="auto", metadata={
        "help": "ward clustering path: Pallas kernel vs core/ward.py "
                "reference", "choices": ("auto", "kernel", "ref")})

    def __post_init__(self):
        if not isinstance(self.method, str) or not self.method:
            raise ValueError(f"pooling method must be a non-empty str, "
                             f"got {self.method!r}")
        if int(self.factor) < 1:
            raise ValueError(f"pool factor must be >= 1, "
                             f"got {self.factor!r}")
        if self.ward_kernel not in ("auto", "kernel", "ref"):
            raise ValueError(f"ward_kernel must be auto|kernel|ref, "
                             f"got {self.ward_kernel!r}")

    def apply(self, x, mask):
        """Pool one encode batch: (x [B,N,d], mask [B,N]) ->
        (pooled, pooled_mask), through the strategy registry."""
        if int(self.factor) <= 1:
            return pooling_strategy("none")(x, mask, 1)
        if self.method == "ward" and "ward" not in _POOLING_REGISTRY:
            # builtin ward carries the kernel/ref toggle; a registered
            # "ward" strategy still shadows the builtin entirely
            from repro.core.pooling import pool_doc_embeddings
            return pool_doc_embeddings(x, mask, int(self.factor), "ward",
                                       ward_kernel=self.ward_kernel)
        return pooling_strategy(self.method)(x, mask, int(self.factor))

    def manifest_meta(self) -> Dict[str, Any]:
        """The ``pool`` entry artifact manifests record — the ONE
        definition every save path embeds (its inverse is
        :func:`retriever_spec_from_manifest`)."""
        # ward_kernel is deliberately ABSENT: both impls write bitwise-
        # identical artifacts, so the toggle is a runtime choice (like
        # ServeSpec), not index content — artifacts stay byte-stable
        # across impl pins and pre-kernel history.
        return {"method": self.method, "factor": int(self.factor)}


@dataclass(frozen=True)
class IndexSpec(_SpecBase):
    """Backend + construction knobs — the single source of truth that
    replaced ``Indexer._index_kw``, ``index.PARAM_KEYS``, and
    ``persist._PARAM_KEYS``. Field defaults are pinned equal to the
    ``MultiVectorIndex`` / ``CascadeIndex`` dataclass defaults by
    tests/test_spec.py, so a default spec builds the default index."""
    backend: str = field(default="plaid", metadata={
        "help": "index backend", "choices": backend_names})
    doc_maxlen: int = 256
    # PLAID
    n_centroids: int = 256
    quant_bits: int = 2
    nprobe: int = 8
    t_cs: float = 0.3
    ndocs: int = 8192
    # HNSW (paper Appendix A)
    hnsw_m: int = 12
    hnsw_ef_construction: int = 200
    hnsw_candidates: int = 1024
    # cascade (beyond-paper; retrieval/cascade.py)
    coarse_factor: int = 6
    fine_factor: int = 2
    candidates: int = 32

    def __post_init__(self):
        if self.backend not in _BACKEND_REGISTRY:
            raise ValueError(f"unknown backend {self.backend!r}; known: "
                             f"{backend_names()}")
        # the packed rerank kernels unpack 32/bits codes per word in
        # fixed-width lanes; only the codec widths they compile for are
        # legal index configurations
        if int(self.quant_bits) not in (2, 4):
            raise ValueError(f"quant_bits must be 2 or 4, got "
                             f"{self.quant_bits!r}")

    @property
    def artifact_kind(self) -> str:
        return backend_info(self.backend).artifact_kind

    def params(self) -> Dict[str, Any]:
        """The construction kwargs for this backend's index class —
        exactly what the persistence manifest records."""
        return {k: getattr(self, k)
                for k in backend_info(self.backend).param_keys}

    def generic_params(self) -> Dict[str, Any]:
        """The :data:`INDEX_PARAM_KEYS` values regardless of backend —
        what a cascade manifest additionally records so
        spec -> manifest -> spec stays a true identity."""
        return {k: getattr(self, k) for k in INDEX_PARAM_KEYS}

    @classmethod
    def from_config(cls, cfg, backend: Optional[str] = None,
                    **overrides) -> "IndexSpec":
        """Lift the retrieval knobs off a ``ColbertConfig``; explicit
        overrides win (the old ``Indexer(**index_kw)`` precedence)."""
        base = dict(backend=backend or cfg.index_backend,
                    doc_maxlen=cfg.doc_maxlen,
                    n_centroids=cfg.n_centroids,
                    quant_bits=cfg.quant_bits,
                    nprobe=cfg.nprobe, t_cs=cfg.t_cs, ndocs=cfg.ndocs)
        base.update(overrides)
        return _from_dict(cls, base)

    @classmethod
    def from_manifest_params(cls, backend: str,
                             params: Dict[str, Any]) -> "IndexSpec":
        """Rebuild from a manifest's ``params`` table. Unknown keys are
        rejected (format drift must not load as garbage); missing keys
        take spec defaults (older artifacts recorded a subset)."""
        unknown = set(params) - set(INDEX_PARAM_KEYS)
        if unknown:
            raise ValueError(f"unknown index params {sorted(unknown)}")
        return cls(backend=backend, **params)


@dataclass(frozen=True)
class ShardSpec(_SpecBase):
    """Streaming-build / sharded-layout knobs (core/sharded.py)."""
    shard_max_vectors: int = field(default=0, metadata={
        "help": "build via the streaming path, flushing a new shard "
                "every N pooled vectors (0 = monolithic)"})
    probe_threads: int = field(default=0, metadata={
        "help": "stage-1 probe workers per sharded index "
                "(0 = auto: min(8, cores); replica routing divides the "
                "auto width across lanes)"})

    def __post_init__(self):
        if int(self.shard_max_vectors) < 0:
            raise ValueError(f"shard_max_vectors must be >= 0, got "
                             f"{self.shard_max_vectors!r}")
        if int(self.probe_threads) < 0:
            raise ValueError(f"probe_threads must be >= 0, got "
                             f"{self.probe_threads!r}")

    @property
    def sharded(self) -> bool:
        return int(self.shard_max_vectors) > 0


@dataclass(frozen=True)
class ServeSpec(_SpecBase):
    """Serving-runtime knobs (launch/engine.py ServingEngine): dynamic
    batcher, shape buckets, and hot-swap watcher. Runtime-only — never
    persisted into artifacts."""
    max_batch: int = field(default=32, metadata={
        "help": "engine coalescing cap / largest shape bucket"})
    max_wait_ms: float = field(default=2.0, metadata={
        "help": "engine batcher flush deadline"})
    k: int = field(default=10, metadata={
        "help": "results returned per query"})
    poll_interval_s: float = field(default=0.2, metadata={
        "cli": False, "help": "index-dir hot-swap poll interval"})
    pipeline_depth: Optional[int] = field(default=None, metadata={
        "cli": False,
        "help": "encode/search overlap depth (None = auto by cores)"})
    warmup_on_start: bool = field(default=True, metadata={
        "cli": False, "help": "trace all shape buckets at start()"})
    n_replicas: int = field(default=1, metadata={
        "help": "replica groups the engine routes microbatches across "
                "(core/replicated.py; 1 = single-lane serving)"})

    def __post_init__(self):
        if int(self.max_batch) < 1:
            raise ValueError(f"max_batch must be >= 1, got "
                             f"{self.max_batch!r}")
        if int(self.n_replicas) < 1:
            raise ValueError(f"n_replicas must be >= 1, got "
                             f"{self.n_replicas!r}")


@dataclass(frozen=True)
class RetrieverSpec(_SpecBase):
    """The whole pipeline, typed: pool -> index -> shard -> serve.
    What ``repro.Retriever.build`` consumes and artifacts round-trip."""
    pooling: PoolingSpec = field(default_factory=PoolingSpec)
    index: IndexSpec = field(default_factory=IndexSpec)
    shard: ShardSpec = field(default_factory=ShardSpec)
    serve: ServeSpec = field(default_factory=ServeSpec)

    def __post_init__(self):
        if self.shard.sharded and self.index.backend == "cascade":
            raise ValueError("cascade indexes have no sharded layout "
                             "(shard_max_vectors must be 0)")

    def to_dict(self) -> Dict[str, Any]:
        return {"pooling": self.pooling.to_dict(),
                "index": self.index.to_dict(),
                "shard": self.shard.to_dict(),
                "serve": self.serve.to_dict()}

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "RetrieverSpec":
        if not isinstance(d, dict):
            raise ValueError(f"RetrieverSpec expects a dict, got {type(d)}")
        unknown = set(d) - {"pooling", "index", "shard", "serve"}
        if unknown:
            raise ValueError(f"unknown RetrieverSpec keys {sorted(unknown)}")
        return cls(
            pooling=PoolingSpec.from_dict(d.get("pooling", {})),
            index=IndexSpec.from_dict(d.get("index", {})),
            shard=ShardSpec.from_dict(d.get("shard", {})),
            serve=ServeSpec.from_dict(d.get("serve", {})))

    @classmethod
    def from_config(cls, cfg, **index_overrides) -> "RetrieverSpec":
        return cls(pooling=PoolingSpec(method=cfg.pool_method,
                                       factor=max(int(cfg.pool_factor), 1)),
                   index=IndexSpec.from_config(cfg, **index_overrides))

    @classmethod
    def coerce(cls, spec, cfg=None) -> "RetrieverSpec":
        """Accept a RetrieverSpec, a bare IndexSpec/PoolingSpec/ShardSpec
        (other parts defaulted from ``cfg``), a dict, or None."""
        if spec is None:
            return cls.from_config(cfg) if cfg is not None else cls()
        if isinstance(spec, cls):
            return spec
        base = cls.from_config(cfg) if cfg is not None else cls()
        if isinstance(spec, IndexSpec):
            return base.replace(index=spec)
        if isinstance(spec, PoolingSpec):
            return base.replace(pooling=spec)
        if isinstance(spec, ShardSpec):
            return base.replace(shard=spec)
        if isinstance(spec, dict):
            full = cls.from_dict(spec)      # validates all sections
            # sections the dict omits default from cfg, same as the
            # bare-spec forms above — not from the class defaults
            return base.replace(**{name: getattr(full, name)
                                   for name in ("pooling", "index",
                                                "shard", "serve")
                                   if name in spec})
        raise TypeError(f"cannot coerce {type(spec).__name__} to "
                        f"RetrieverSpec")


# ---------------------------------------------------------------------------
# Manifest round-trip
# ---------------------------------------------------------------------------
def manifest_meta_for(spec: RetrieverSpec) -> Dict[str, Any]:
    """The spec-relevant subset of the manifest meta the save paths
    write for ``spec`` — the inverse of
    :func:`retriever_spec_from_manifest`. tests/test_spec.py pins both
    the pure round-trip (hypothesis) and, in tests/test_api.py, that
    REAL artifacts written by ``Retriever.build`` carry exactly these
    entries."""
    meta: Dict[str, Any] = {
        "kind": spec.index.artifact_kind,
        "pool": spec.pooling.manifest_meta(),
    }
    if spec.index.backend == "cascade":
        meta.update({k: getattr(spec.index, k)
                     for k in CASCADE_PARAM_KEYS})
        # the generic knobs don't drive a cascade build, but recording
        # them keeps spec -> manifest -> spec a true identity
        meta["params"] = spec.index.generic_params()
    else:
        meta["backend"] = spec.index.backend
        meta["params"] = spec.index.params()
        if spec.shard.sharded:
            meta["kind"] = "sharded_index"
            meta["shard_max_vectors"] = int(spec.shard.shard_max_vectors)
            # auto (0) is the long-standing default: written only when
            # pinned, so pre-existing artifacts hash/compare unchanged
            if int(spec.shard.probe_threads) > 0:
                meta["probe_threads"] = int(spec.shard.probe_threads)
    return meta


def retriever_spec_from_manifest(manifest: Dict[str, Any],
                                 serve: Optional[ServeSpec] = None
                                 ) -> RetrieverSpec:
    """Rebuild the build-time spec from an artifact manifest.

    Serving knobs are runtime-only (never persisted), so ``serve``
    comes back default unless the caller provides one.
    """
    kind = manifest.get("kind")
    pool_meta = manifest.get("pool")
    pooling = (PoolingSpec.from_dict(pool_meta) if pool_meta
               else PoolingSpec())
    shard = ShardSpec()
    if kind == "cascade_index":
        index = IndexSpec.from_manifest_params(
            "cascade", dict(manifest.get("params", {}))).replace(**{
                k: manifest[k] for k in CASCADE_PARAM_KEYS
                if k in manifest})
    elif kind in ("multi_vector_index", "sharded_index"):
        index = IndexSpec.from_manifest_params(
            manifest.get("backend", "plaid"),
            dict(manifest.get("params", {})))
        if kind == "sharded_index":
            shard = ShardSpec(
                shard_max_vectors=int(manifest.get("shard_max_vectors", 0)),
                probe_threads=int(manifest.get("probe_threads", 0)))
    else:
        raise ValueError(f"manifest kind {kind!r} carries no retriever "
                         f"spec")
    return RetrieverSpec(pooling=pooling, index=index, shard=shard,
                         serve=serve or ServeSpec())


# ---------------------------------------------------------------------------
# Argparse derivation: flags FROM the spec, not beside it
# ---------------------------------------------------------------------------
def add_spec_args(parser, spec_cls, prefix: str = "",
                  defaults: Optional[Dict[str, Any]] = None,
                  only: Optional[Sequence[str]] = None):
    """Add one ``--flag`` per CLI-eligible field of ``spec_cls``.

    Flag name = ``--{prefix}{field}`` with underscores dashed; type and
    default come from the dataclass, help/choices from field metadata
    (``choices`` may be a callable so registry growth shows up).
    ``defaults`` overrides per-call defaults (e.g. serve.py's
    ``--pool-factor 2``); ``only`` restricts to a subset. Parse back
    with :func:`spec_from_args`.
    """
    defaults = defaults or {}
    for f in dataclasses.fields(spec_cls):
        if f.metadata.get("cli") is False:
            continue
        if only is not None and f.name not in only:
            continue
        default = defaults.get(f.name, f.default)
        kw: Dict[str, Any] = {
            "default": default,
            "help": f.metadata.get("help", f.name)
            + f" (default: {default})",
        }
        choices = f.metadata.get("choices")
        if callable(choices):
            choices = choices()
        if choices:
            kw["choices"] = choices
        if not isinstance(default, bool) and isinstance(
                default, (int, float, str)):
            kw["type"] = type(default)
        flag = "--" + (prefix + f.name).replace("_", "-")
        parser.add_argument(flag, **kw)
    return parser


def spec_from_args(spec_cls, args, prefix: str = "",
                   only: Optional[Sequence[str]] = None, **overrides):
    """Collect a spec back out of parsed args (inverse of
    :func:`add_spec_args`); fields without a matching arg keep their
    defaults, explicit ``overrides`` win."""
    kw: Dict[str, Any] = {}
    for f in dataclasses.fields(spec_cls):
        if f.metadata.get("cli") is False:
            continue
        if only is not None and f.name not in only:
            continue
        attr = (prefix + f.name).replace("-", "_")
        if hasattr(args, attr):
            kw[f.name] = getattr(args, attr)
    kw.update(overrides)
    return spec_cls(**kw)
