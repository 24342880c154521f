"""Engine protocol + string-keyed registry of retrieval backends.

Every engine adapts one existing traversal entry point to the uniform
``search(terms, weights_b, weights_l, dense, *, k, params)`` contract and
returns a ``core.traversal.RetrievalResult``. All sparse engines are
driven by the same ``core.plan`` planner — registering an engine selects
an *executor/placement*, never a different pruning algorithm:

    "batched"     vmap x lax.scan tile scan (jnp scorer)      1 device
    "kernel"      same scan, fused Pallas guided_score scorer 1 device
    "sequential"  host tile loop, physical skips + timings    1 device
    "sharded"     shard_map tile ranges + collective merge    mesh
    "dense"       blocked dense two-level pruning             1 device
    "cascade"     sparse traversal at depth k' -> dense rerank to k
    "rrf"         reciprocal-rank fusion of sparse + dense rankings

The hybrid engines (``cascade`` / ``rrf``) open on a
:class:`~repro.retrieval.hybrid.HybridIndex` (sparse BII + dense doc
embeddings + query projection); every *sparse* engine also accepts a
HybridIndex and transparently serves its ``.sparse`` side, so one
scheduler index can back a routing policy that mixes sparse and hybrid
routes.

Third-party backends register with ``@register_engine("name")`` — the
class must accept ``(index, params, **opts)`` and implement ``search``.
"""
from __future__ import annotations

from typing import Protocol, runtime_checkable

import numpy as np

from ..core.dense_guided import DenseGuidedIndex, retrieve_dense_batched
from ..core.index import BlockedImpactIndex
from ..core.traversal import (RetrievalResult, retrieve_batched,
                              retrieve_sequential)
from ..core.twolevel import TwoLevelParams
from .contract import K_BUCKETS, bucket_k
from .hybrid import (HybridIndex, dense_topk, embed_queries,
                     rerank_candidates, rrf_fuse)

_REGISTRY: dict[str, type] = {}


def register_engine(name: str):
    """Class decorator: register an Engine implementation under ``name``."""
    def deco(cls):
        cls.name = name
        _REGISTRY[name] = cls
        return cls
    return deco


def engine_names() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def get_engine(name: str) -> type:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown engine {name!r}; registered engines: "
                       f"{', '.join(engine_names())}") from None


@runtime_checkable
class Engine(Protocol):
    """What the Retriever facade drives. ``search`` executes one batch at
    depth ``k`` under pruning policy ``params`` and returns the raw
    engine result (internal ids already mapped to original docid space).

    ``replicate`` returns a fresh instance with the same configuration
    **sharing the open index arrays** (no rebuild, no re-partition) —
    what the serving executor pool clones per worker. Engines hold no
    per-call mutable state, so a replica is just a second dispatch
    surface over the same device buffers."""
    name: str

    def search(self, terms, weights_b, weights_l, dense, *, k: int,
               params: TwoLevelParams) -> RetrievalResult:
        ...

    def replicate(self, params: TwoLevelParams) -> "Engine":
        ...


def _require_bii(index, engine: str) -> BlockedImpactIndex:
    from ..index.compressed import CompressedImpactIndex
    if isinstance(index, HybridIndex):
        index = index.sparse   # sparse engines serve the sparse side
    if not isinstance(index, (BlockedImpactIndex, CompressedImpactIndex)):
        raise TypeError(f"engine {engine!r} needs a BlockedImpactIndex or "
                        f"CompressedImpactIndex, got {type(index).__name__}")
    return index


def _require_hybrid(index, engine: str) -> HybridIndex:
    if not isinstance(index, HybridIndex):
        raise TypeError(
            f"engine {engine!r} needs a HybridIndex (sparse BII + dense "
            f"doc embeddings; see repro.retrieval.build_hybrid_index), "
            f"got {type(index).__name__}")
    return index


@register_engine("batched")
class BatchedEngine:
    """vmap-over-queries lax.scan tile scan; pure-jnp tile scorer.

    ``traversal="chunked"`` replaces the all-tiles scan with the
    descending-bound chunk loop (``lax.while_loop`` with early exit):
    bit-identical to the ``impact``-schedule full scan while dispatching
    only the live chunk prefix; stats gain ``chunks_dispatched``.
    ``chunk_tiles`` overrides ``params.chunk_tiles``.
    """

    use_kernel = False
    traversals = ("full", "chunked")

    # NOTE: engines deliberately hold no pruning params — the policy for
    # each call arrives via search(params=...) (possibly with a per-call
    # threshold_factor override), so storing the open-time copy would
    # only invite stale reads.
    def __init__(self, index, params: TwoLevelParams,
                 traversal: str = "full", chunk_tiles: int | None = None):
        self.index = _require_bii(index, self.name)
        if traversal not in self.traversals:
            raise ValueError(
                f"engine {self.name!r} supports traversal in "
                f"{self.traversals}, got {traversal!r}")
        self.traversal = traversal
        self.chunk_tiles = chunk_tiles

    def search(self, terms, weights_b, weights_l, dense, *, k, params):
        return retrieve_batched(self.index, terms, weights_b, weights_l,
                                params, use_kernel=self.use_kernel, k=k,
                                traversal=self.traversal,
                                chunk_tiles=self.chunk_tiles)

    def replicate(self, params):
        return type(self)(self.index, params, traversal=self.traversal,
                          chunk_tiles=self.chunk_tiles)


@register_engine("kernel")
class KernelEngine(BatchedEngine):
    """Batched scan routed through the fused Pallas guided_score kernel
    (native on TPU, interpreter elsewhere). ``traversal="chunked"`` keeps
    the per-tile kernel inside the chunk loop (bit-identical early exit);
    ``"chunked_fused"`` scores each chunk with one multi-tile
    ``guided_score_chunk`` pallas_call (chunk-start thresholds: rank-safe
    exact, guided within the usual tolerance)."""

    use_kernel = True
    traversals = ("full", "chunked", "chunked_fused")


@register_engine("sequential")
class SequentialEngine:
    """Host-driven per-query loop with physical tile skips; the paper's
    single-threaded latency regime. Responses carry per-query timings."""

    def __init__(self, index, params: TwoLevelParams, warmup: bool = True):
        self.index = _require_bii(index, self.name)
        self.warmup = warmup

    def search(self, terms, weights_b, weights_l, dense, *, k, params):
        return retrieve_sequential(self.index, terms, weights_b, weights_l,
                                   params, warmup=self.warmup, k=k)

    def replicate(self, params):
        return type(self)(self.index, params, warmup=self.warmup)


@register_engine("sharded")
class ShardedEngine:
    """Mesh-sharded tile ranges with a collective top-k merge.

    Accepts a ``BlockedImpactIndex`` (partitioned here via ``n_shards``)
    or a prebuilt ``core.shard_plan.ShardedImpactIndex``. With a mesh, the
    stacked shard leaves are placed on it here, once; ``mesh=None``
    serves through the single-device vmap emulation path.
    """

    def __init__(self, index, params: TwoLevelParams, *,
                 n_shards: int | None = None, mesh=None,
                 axis_name: str = "shard", use_kernel: bool = False,
                 exchange_every: int = 0, traversal: str = "full",
                 chunk_tiles: int | None = None):
        # deferred: serve.sharded imports serve.engine, which uses the
        # Retriever facade — a module-level import here would be circular
        from ..core.shard_plan import (ShardedImpactIndex, place_on_mesh,
                                       shard_index)
        if traversal not in ("full", "chunked"):
            raise ValueError(f"engine {self.name!r} supports traversal in "
                             f"('full', 'chunked'), got {traversal!r}")
        if mesh is not None and n_shards is None:
            n_shards = mesh.shape[axis_name]
        if isinstance(index, ShardedImpactIndex):
            self.sharded = index
        else:
            self.sharded = shard_index(_require_bii(index, self.name),
                                       n_shards or 1)
        if mesh is not None:
            # a replica hands over an already placed index: device_put to
            # the sharding it already has moves nothing
            self.sharded = place_on_mesh(self.sharded, mesh, axis_name)
        self.mesh = mesh
        self.axis_name = axis_name
        self.use_kernel = use_kernel
        self.exchange_every = exchange_every
        self.traversal = traversal
        self.chunk_tiles = chunk_tiles

    def search(self, terms, weights_b, weights_l, dense, *, k, params):
        from ..serve.sharded import shard_retrieve_batched
        return shard_retrieve_batched(
            self.sharded, terms, weights_b, weights_l, params,
            mesh=self.mesh, axis_name=self.axis_name,
            use_kernel=self.use_kernel,
            exchange_every=self.exchange_every, k=k,
            traversal=self.traversal, chunk_tiles=self.chunk_tiles)

    def replicate(self, params):
        # hand over the prebuilt ShardedImpactIndex: a replica must never
        # re-partition the tile ranges (stacked shard arrays are the
        # expensive part of open)
        return type(self)(self.sharded, params, mesh=self.mesh,
                          axis_name=self.axis_name,
                          use_kernel=self.use_kernel,
                          exchange_every=self.exchange_every,
                          traversal=self.traversal,
                          chunk_tiles=self.chunk_tiles)


@register_engine("dense")
class DenseEngine:
    """2GTI transferred to blocked dense retrieval (two-tower candidates).

    Queries arrive as ``SearchRequest.dense`` [B, D] embeddings and the
    whole batch runs through one jitted guided block scan
    (``core.dense_guided.retrieve_dense_batched`` — a vmap over the
    per-query scan, so each row keeps its own block order/thresholds and
    results match the per-query path). ``threshold_factor`` overrides
    are ignored — the dense skip test has no factor knob."""

    def __init__(self, index, params: TwoLevelParams):
        if isinstance(index, HybridIndex):
            index = index.dense   # dense-only lane of a hybrid index
        if not isinstance(index, DenseGuidedIndex):
            raise TypeError(f"engine 'dense' needs a DenseGuidedIndex "
                            f"(core.dense_guided.build_dense_index), got "
                            f"{type(index).__name__}")
        self.index = index

    def search(self, terms, weights_b, weights_l, dense, *, k, params):
        if dense is None:
            raise ValueError("engine 'dense' reads SearchRequest.dense "
                             "([B, D] query embeddings); got None")
        scores, ids, stats = retrieve_dense_batched(self.index, dense,
                                                    params, k=k)
        ids = ids.astype(np.int32)
        scores = scores.astype(np.float32)
        return RetrievalResult(ids=ids, scores=scores, global_ids=ids,
                               local_ids=ids, stats=stats)

    def replicate(self, params):
        return type(self)(self.index, params)


_HYBRID_FIRST_STAGES = ("batched", "kernel", "sequential", "sharded")


class _HybridBase:
    """Shared open-time plumbing of the two hybrid engines: a HybridIndex,
    a sparse first stage from the registry, and a candidate depth k'.

    ``depth`` (k') is bucketed at call time together with the requested
    k, so the jitted stages compile once per (k'-bucket, k-bucket) pair
    — a per-call k sweep never retraces either stage. Extra ``**opts``
    go to the first-stage constructor (``traversal="chunked"``,
    ``n_shards=...``, ...)."""

    def __init__(self, index, params: TwoLevelParams, *,
                 depth: int = 100, first_stage: str = "batched", **opts):
        self.hybrid = _require_hybrid(index, self.name)
        if first_stage not in _HYBRID_FIRST_STAGES:
            raise ValueError(
                f"engine {self.name!r} first_stage must be in "
                f"{_HYBRID_FIRST_STAGES}, got {first_stage!r}")
        if depth < 1:
            raise ValueError(f"depth={depth} must be >= 1")
        self.depth = int(depth)
        self.first = get_engine(first_stage)(self.hybrid.sparse, params,
                                             **opts)
        # remembered for replicate(): the executor pool re-opens the same
        # configuration over the shared HybridIndex
        self._first_stage = first_stage
        self._first_opts = dict(opts)

    def replicate(self, params):
        return type(self)(self.hybrid, params, depth=self.depth,
                          first_stage=self._first_stage,
                          **self._replicate_opts())

    def _replicate_opts(self) -> dict:
        return dict(self._first_opts)

    def _depth_for(self, k: int) -> int:
        """Candidate depth of one call: at least the configured k' and
        the requested k, bucketed (and corpus-capped) so the static
        stage shapes stay on the compile grid."""
        return min(bucket_k(max(self.depth, k), K_BUCKETS),
                   self.hybrid.n_docs)


@register_engine("cascade")
class CascadeEngine(_HybridBase):
    """Sparse guided traversal at depth k', exact-dense rerank to k.

    Stage one is any sparse registry engine on the shared planner (the
    pruning policy — including per-call ``threshold_factor`` overrides —
    applies there); stage two gathers the k' candidates' embedding rows
    through the hybrid index and takes the exact dense top-k (jitted,
    ``hybrid.rerank_candidates``). Query embeddings come from
    ``SearchRequest.dense`` when provided, else from the sparse query
    via the index's ``q_proj`` bridge — so the engine serves plain
    sparse requests end-to-end (scheduler routing included). Scores in
    the response are *dense* scores, not RankScores."""

    def search(self, terms, weights_b, weights_l, dense, *, k, params):
        k1 = self._depth_for(k)
        res = self.first.search(terms, weights_b, weights_l, None,
                                k=k1, params=params)
        q_rot = embed_queries(self.hybrid, terms, weights_l, dense=dense)
        scores, ids = rerank_candidates(self.hybrid, q_rot,
                                        np.asarray(res.ids), k=k)
        stats = dict(res.stats)
        stats["cascade_depth"] = float(k1)
        return RetrievalResult(ids=ids, scores=scores, global_ids=ids,
                               local_ids=ids, stats=stats,
                               latencies_ms=res.latencies_ms)


@register_engine("rrf")
class RRFEngine(_HybridBase):
    """Reciprocal-rank fusion of the sparse and dense rankings.

    Both legs rank to depth k' (sparse: first-stage traversal under the
    pruning policy; dense: batched exact top-k' over the embedding
    table), then fuse with ``score(d) = sum 1/(rrf_k + rank_d)`` and
    keep the top k. Response scores are RRF scores — comparable within
    a response, not across engines."""

    def __init__(self, index, params: TwoLevelParams, *,
                 depth: int = 100, rrf_k: float = 60.0,
                 first_stage: str = "batched", **opts):
        super().__init__(index, params, depth=depth,
                         first_stage=first_stage, **opts)
        if rrf_k <= 0:
            raise ValueError(f"rrf_k={rrf_k} must be > 0")
        self.rrf_k = float(rrf_k)

    def _replicate_opts(self) -> dict:
        return {**self._first_opts, "rrf_k": self.rrf_k}

    def search(self, terms, weights_b, weights_l, dense, *, k, params):
        k1 = self._depth_for(k)
        res = self.first.search(terms, weights_b, weights_l, None,
                                k=k1, params=params)
        q_rot = embed_queries(self.hybrid, terms, weights_l, dense=dense)
        _, dense_ids = dense_topk(self.hybrid, q_rot, k=k1)
        ids, scores = rrf_fuse(np.asarray(res.ids), dense_ids, k=k,
                               rrf_k=self.rrf_k)
        stats = dict(res.stats)
        stats["fusion_depth"] = float(k1)
        stats["rrf_k"] = self.rrf_k
        return RetrievalResult(ids=ids, scores=scores, global_ids=ids,
                               local_ids=ids, stats=stats,
                               latencies_ms=res.latencies_ms)
