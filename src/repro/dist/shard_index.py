"""Doc-sharded two-phase vector search (the Elasticsearch scaling story).

:class:`ShardedVectorIndex` is :class:`repro.core.VectorIndex` split into
contiguous *doc-shards* along the mesh's ``data`` axis, one shard per
device.  A query runs the ES distributed query/fetch protocol:

1. **query phase** (per shard, under ``shard_map``): phase-1 scoring over
   the local codes (or posting lists, for the ``postings`` engine), local
   ``top_k(page)``, exact-cosine scoring of the local candidate page;
2. **merge phase**: per-shard candidate pages reach the coordinating
   reduce (ids are globalised by the shard's doc-id offset) and a global
   ``top_k(k)`` over the exact cosines picks the final hits.

Two merge transports implement step 2 (``search(..., merge=...)``):

* ``"gather"`` -- one blocking all-gather of every shard's page, then a
  flat global top-k (the PR-1 path; peak buffer ``S * page`` per query).
* ``"stream"`` -- candidate pages ring-rotate along the ``data`` axis
  (``ppermute``) and *stream* into a running top-k one shard at a time:
  the group coordinator (data index 0) folds pages in shard order, so
  communication of page ``t+1`` overlaps the merge of page ``t`` and the
  peak buffer is ``k + page`` regardless of shard count.  Tie-breaks
  replicate the flat gather's shard-major order, so both transports
  return identical hits.

**Replica tier** (ES replica shards): on a 2-D ``(data, replica)`` mesh
(:func:`repro.launch.mesh.make_shard_mesh` with ``n_replicas > 1``) every
index leaf is replicated across the ``replica`` axis -- R full copies of
the doc-sharded corpus.  Incoming query batches round-robin across replica
groups (the batch splits along ``replica`` in the ``shard_map`` in-spec),
each group runs the full query/fetch protocol against its own copy, and
per-replica results are bit-identical to the single-replica path: QPS
scales ~R x while quality is untouched (``page >= n_docs`` parity holds
per group).  Batches are zero-padded up to a multiple of R and the pad
rows sliced off after the merge, so they can never leak into results.

Two control-plane entry points sit on top of the replica tier:

* :meth:`replica_group` makes the groups *addressable*: it views one
  replica column as an independent 1-D ``data``-mesh index (the leaves are
  already resident on that column's devices, so the re-put is free).  The
  cluster router (:mod:`repro.cluster.router`) fronts each group with its
  own request batcher, which is what lets concurrent QPS scale with R
  instead of materialising only inside a single batch.
* ``search(..., live_groups=...)`` is the *health-masked merge*: query
  blocks are assigned only to the named (healthy) replica columns, dead
  columns receive zero rows, and the out-rows of the live columns are
  gathered back into query order before the final rescore -- so a dead
  group's doc range is transparently served by the surviving replicas and
  the results match the healthy cluster (every group holds a full,
  bit-identical copy).

**On-device sharded build** (:meth:`ShardedVectorIndex.build_sharded`):
raw vectors are ``device_put`` straight onto the ``data`` axis and ONE
jitted SPMD program runs the whole pipeline per shard under ``shard_map``
-- normalize -> ``encoder.encode`` -> ``index_best`` sentinel masking ->
the df table counted off the codes -- so index construction scales with
the mesh exactly like search does.  :meth:`from_index` (partitioning an
existing single-device index) likewise counts the per-shard df tables in
one SPMD program; neither path loops over shards on the host.

**Posting lists on demand**: the base's per-shard posting lists
(``post_docs``/``post_codes``, a (C, dp) int32 and a (C, dp) code table
per shard -- five times the bytes of int8 codes) are a derived
cache of the base codes, like the int8 quant tables.  They are sorted on
first use only, by what reads them: the ``postings`` engine,
:attr:`max_df` (``max_postings="auto"``), a df read where the code range
is too wide for a table, or a caller reading the properties.  The
``fused`` engines and every df read through a table never build them.
Each build counts in ``index.postings.builds`` and runs in the host span
``repro.index.postings``.

**Incremental ingest** (the full Lucene segment story):

* :meth:`add_documents` appends new docs to a per-shard *active append
  buffer* (round-robin shard routing, monotonically growing global ids
  starting at ``n_docs``).  The buffer carries codes but no posting lists;
  its phase-1 scores come from a direct per-column bucket-equality match
  (the same score every engine computes) and its df joins the global psum
  through :func:`repro.core.postings.code_df`.
* Once the buffer reaches ``seal_threshold`` rows it SEALS into an
  immutable :class:`Segment` (a Lucene segment/generation): truncated to
  its exact width, with its own mini posting table and df table, and a
  fresh active buffer opens.  Search scores base + N sealed
  generations + the active buffer under ONE jitted SPMD program with
  per-generation live masks -- candidate order is append order per shard,
  which keeps results bit-identical to the flat single-buffer path at
  every (k, page).
* :meth:`merge_segments` is the Lucene background merge: a contiguous run
  of sealed generations re-packs into one (tombstoned rows dropped and
  reclaimed, ids and vector bits preserved) -- the operation the cluster
  tier's ``TieredMergePolicy`` schedules off the query path, demoting full
  :meth:`compact` to a delete-pressure last resort.
* :meth:`delete` marks docs dead: the per-doc ``live`` mask goes False,
  the doc's codes become the sentinel, and the affected df tables are
  counted again off the new codes (sealed segments also re-sort their
  mini posting lists; base posting lists are dropped, to be sorted again
  on demand) -- so document frequencies are EXACT under tombstones
  (idf-sensitive engines score identically before and after
  :meth:`compact`), unlike Lucene's lazy semantics where df transiently
  counts deleted docs.  The ``live`` mask stays the source of truth for
  result eligibility.  Each shard's tombstone count is tracked host-side
  (``shard_tombstones``); ``tombstone_ratio`` is the worst per-shard dead
  fraction, the trigger the cluster maintenance daemon
  (:mod:`repro.cluster.maintenance`) watches for background
  auto-compaction.
* :meth:`compact` folds segments and tombstones back into a clean base by
  re-running the on-device sharded build over the live doc table.  Global
  ids are stable across compaction: dead ids simply stop existing (their
  rows become sentinel-coded padding).

BUILD/INGEST INVARIANTS (relied on throughout):

* *Sentinel-tail postings*: padded and tombstoned rows carry the
  never-matching sentinel code, which sorts to the tail of every posting
  list and counts only under the df table's sentinel entry -- range
  lookups and table reads cannot reach them, and a legal query code can
  never equal the sentinel.
* *Unsharded final rescore*: reported scores always come from the
  canonical ``(Q, k, n)`` einsum with unsharded operands on the
  coordinating device (see ``_merge_phase``) -- GSPMD blocks a sharded
  einsum differently per mesh shape, which would cost last-ulp parity.
* *Segment/tombstone semantics*: empty segment slots and tombstones are
  sentinel-coded and ``live=False``; ``live`` is the source of truth for
  result eligibility.  When fewer than ``k`` live docs exist, unfillable
  result slots report ``(id=-1, score=-inf)``.

IDF query weighting stays *global*: each shard reads its tokens' document
frequencies from its df table (:func:`repro.core.postings.build_df_table`,
counted off the codes), and they are summed across shards with a ``psum``
over ``data`` (integer-exact, identical in every
replica group), so trimming/weighting decisions are independent of both
the shard count and the replica count.  ``N`` is the global id-space size
(``n_docs`` + docs ever appended), ES ``maxDoc`` style.

Ragged corpora pad each shard to a common length; padded rows carry a
never-matching sentinel code, score ``-inf`` in both phases, and can never
enter the merged top-k.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import time
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.core.encoding import Encoder, RoundingEncoder
from repro.obs.compile_watch import watch_metrics, watch_region
from repro.obs.tracing import annotation
from repro.core.filtering import BestFilter, TrimFilter, index_best_codes
from repro.core.postings import (Postings, build_df_table, build_postings,
                                 code_df, idf_weights, table_df)
from repro.core.quantize import quantize_rows
from repro.core.rerank import EXACT, normalize
from repro.core.search import (_SENTINEL, FUSED_ENGINES, VectorIndex,
                               encode_query_rows, phase1_engine_scores)

from .sharding import DATA_AXIS, REPLICA_AXIS

__all__ = ["ShardedVectorIndex", "Segment", "DEFAULT_SEAL_THRESHOLD"]


def _put(mesh: Mesh, x, spec: P):
    return jax.device_put(jnp.asarray(x), NamedSharding(mesh, spec))


_ROW = P(DATA_AXIS, None, None)
_VEC = P(DATA_AXIS, None)

# Active append buffers seal into an immutable Segment once they reach this
# many rows.  Below it a direct per-column bucket match over the buffer is
# cheaper than maintaining posting lists; past it the segment gets its own
# mini posting table for O(log G) df lookups.  None disables sealing (the
# pre-generational flat behaviour, which the parity tests pin against).
DEFAULT_SEAL_THRESHOLD = 256


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Segment:
    """One immutable sealed generation of appended docs (a Lucene segment).

    Sealed off the active append buffer once it outgrows the direct-match
    threshold: rows are truncated to their exact round-robin width and the
    segment gets its own mini posting table and df table (one-program SPMD
    builds, as for the base's df table), so its document frequencies come
    from one table read instead of an O(G * C) dense count.
    Phase-1 *scores* stay the direct bucket-equality match -- the identity
    every engine lowers to -- which is what keeps segmented search
    bit-identical to the flat append path at every (k, page).

    Segments are immutable in the Lucene sense: the only mutations are
    tombstoning through :meth:`ShardedVectorIndex.delete` (live -> False,
    sentinel codes, mini postings and df table rebuilt so df stays exact)
    and wholesale replacement by :meth:`ShardedVectorIndex.merge_segments`.
    ``n_rows`` and ``tombstones`` are host-side ints (never cross jit)
    feeding the tiered merge policy's per-segment deleted-doc ratios.
    """

    vectors: jnp.ndarray     # (S, G, n) f32 unit rows; zero rows pad
    codes: jnp.ndarray       # (S, G, C) int; sentinel = dead/padding
    gids: jnp.ndarray        # (S, G) int32 global ids; -1 = padding
    live: jnp.ndarray        # (S, G) bool
    post_docs: jnp.ndarray   # (S, C, G) int32 mini posting order
    post_codes: jnp.ndarray  # (S, C, G) sorted codes per shard
    df_table: jnp.ndarray    # (S, C, W) int32 df by code (build_df_table)
    n_rows: int              # rows holding a doc (live or tombstoned)
    tombstones: int          # dead rows among n_rows

    def tree_flatten(self):
        children = (self.vectors, self.codes, self.gids, self.live,
                    self.post_docs, self.post_codes, self.df_table)
        return children, (self.n_rows, self.tombstones)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def width(self) -> int:
        """Per-shard slot width (= ceil(n_rows / n_shards) at seal/merge)."""
        return self.vectors.shape[1]

    @property
    def deleted_ratio(self) -> float:
        """Dead fraction of this segment's rows -- the per-segment signal
        the tiered merge policy consults (the whole-index
        ``tombstone_ratio`` can't see which generation the deletes hit)."""
        return self.tombstones / max(self.n_rows, 1)

    def quantized(self, mesh: Mesh):
        """Per-row int8 quantization of this segment's vectors for
        ``fused_int8`` phase-1 -- (codes (S,G,n) int8, scale (S,G),
        zero (S,G)), derived lazily and cached on the segment object
        (segments are immutable; tombstoning replaces the object but
        carries the cache, since the vector bits are untouched).
        Quantization is row-wise, so a row's int8 codes are identical
        here and in the flat append buffer -- the seg-vs-flat parity
        pin extends to the quantized engine for free."""
        cached = self.__dict__.get("_quant_cache")
        if cached is None:
            cached = _quantize_program(self.vectors, mesh=mesh)
            self.__dict__["_quant_cache"] = cached
        return cached


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ShardedVectorIndex:
    """A :class:`VectorIndex` partitioned into per-device doc-shards.

    Array leaves carry an explicit leading shard dim (``n_shards`` first)
    and live sharded over the ``data`` mesh axis; each device holds one
    contiguous document range plus its local->global id ``offset``.  The
    ``seg_*`` leaves are the per-shard append segments of incremental
    ingest (width 0 for a freshly built index); ``live`` is the per-doc
    eligibility mask (False = pad or tombstone).  The base posting lists
    are not leaves: :attr:`post_docs` / :attr:`post_codes` sort them from
    the codes on first read and cache them per instance.
    """

    vectors: jnp.ndarray      # (S, dp, n) f32, unit rows; zero rows pad
    codes: jnp.ndarray        # (S, dp, C) int; sentinel rows pad/tombstone
    df_table: jnp.ndarray     # (S, C, W) int32 per-shard df by code; W = 0
    #                           where the code range is too wide to tabulate
    offsets: jnp.ndarray      # (S,) int32 global id of each shard's doc 0
    live: jnp.ndarray         # (S, dp) bool -- False = pad or tombstone
    seg_vectors: jnp.ndarray  # (S, G, n) f32 ACTIVE append-buffer vectors
    seg_codes: jnp.ndarray    # (S, G, C) int; sentinel = empty/tombstone
    seg_gids: jnp.ndarray     # (S, G) int32 global ids; -1 = never used
    seg_live: jnp.ndarray     # (S, G) bool
    segments: Tuple[Segment, ...]  # sealed generations, oldest first
    encoder: Encoder
    mesh: Mesh
    n_docs: int               # base id-space size (compaction folds segs in)
    index_best: Optional[int]
    n_appended: int = 0       # docs ever appended since the last compact
    shard_tombstones: Tuple[int, ...] = ()  # per-shard uncompacted deletes
    seal_threshold: Optional[int] = DEFAULT_SEAL_THRESHOLD
    seg_base: int = 0         # append counter at the active buffer's start
    active_tombstones: int = 0  # dead rows in the active buffer

    # -- pytree plumbing (mesh/encoder/sizes are static metadata) ----------
    def tree_flatten(self):
        children = (self.vectors, self.codes, self.df_table, self.offsets,
                    self.live, self.seg_vectors, self.seg_codes,
                    self.seg_gids, self.seg_live, self.segments)
        return children, (self.encoder, self.mesh, self.n_docs,
                          self.index_best, self.n_appended,
                          self.shard_tombstones, self.seal_threshold,
                          self.seg_base, self.active_tombstones)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    # ------------------------------------------------------------ properties
    @property
    def n_shards(self) -> int:
        return self.vectors.shape[0]

    @property
    def n_replicas(self) -> int:
        if REPLICA_AXIS in self.mesh.axis_names:
            return int(self.mesh.shape[REPLICA_AXIS])
        return 1

    @property
    def docs_per_shard(self) -> int:
        return self.vectors.shape[1]

    @property
    def n_features(self) -> int:
        return self.vectors.shape[2]

    @property
    def seg_capacity(self) -> int:
        """ACTIVE append-buffer slots per shard (0 = no open buffer)."""
        return self.seg_vectors.shape[1]

    @property
    def n_ids(self) -> int:
        """Global id-space size: base docs + docs ever appended."""
        return self.n_docs + self.n_appended

    @property
    def n_tombstones(self) -> int:
        """Docs deleted since the last compaction (whole index)."""
        return sum(self.shard_tombstones)

    @property
    def n_segments(self) -> int:
        """Sealed generations currently serving alongside the base."""
        return len(self.segments)

    @property
    def n_active(self) -> int:
        """Docs in the active (unsealed) append buffer."""
        return self.n_appended - self.seg_base

    @property
    def segment_rows(self) -> int:
        """Rows held by sealed segments (tombstoned rows included)."""
        return sum(s.n_rows for s in self.segments)

    @property
    def n_reclaimed(self) -> int:
        """Appended rows dropped by segment merges since the last compact
        (they no longer occupy slots anywhere; their ids stay retired)."""
        return self.n_appended - self.n_active - self.segment_rows

    @staticmethod
    def _seg_slots_used(n_appended: int, ns: int) -> np.ndarray:
        """(S,) append-segment slots used per shard.  THE round-robin
        occupancy formula -- shared by ingest routing and the tombstone
        accounting so the two can never diverge."""
        used = np.full(ns, n_appended // ns, np.int64)
        used[: n_appended % ns] += 1
        return used

    @property
    def shard_populations(self) -> np.ndarray:
        """(S,) docs ever assigned to each shard (base + appended) -- a pure
        function of the contiguous base split and round-robin ingest
        routing, so no device readback."""
        ns, dp = self.n_shards, self.docs_per_shard
        base = np.clip(self.n_docs - np.arange(ns) * dp, 0, dp)
        app = self._seg_slots_used(self.n_active, ns)
        for s in self.segments:
            # each generation is round-robin within itself (sealed buffers
            # by construction, merged segments by re-packing), so the same
            # occupancy formula applies per segment
            app = app + self._seg_slots_used(s.n_rows, ns)
        return base + app

    @property
    def tombstone_ratio(self) -> float:
        """Worst per-shard dead fraction (ES ``deletes_pct_allowed`` style:
        deleted / docs-ever-assigned, per shard, max over shards) -- the
        signal the cluster maintenance daemon compares against its
        auto-compaction threshold."""
        if not any(self.shard_tombstones):
            return 0.0
        dead = np.asarray(self.shard_tombstones, np.float64)
        return float(np.max(dead / np.maximum(self.shard_populations, 1)))

    @property
    def max_df(self) -> int:
        """Longest live posting list over every (shard, column): the exact
        per-shard ``max_postings`` window -- sized from the shard's actual
        code distribution instead of the ``docs_per_shard`` worst case.
        Reads the base posting lists, so builds them if nothing has yet.
        Tombstone-free by construction (tombstones carry the sentinel,
        which is excluded), cached per instance (every mutation returns a
        new index, so the cache can never go stale)."""
        cached = self.__dict__.get("_max_df_cache")
        if cached is None:
            cached = int(_max_df_program(
                self.post_codes, mesh=self.mesh,
                sentinel=int(_SENTINEL[self.codes.dtype])))
            self.__dict__["_max_df_cache"] = cached
        return cached

    # --------------------------------------------------- base posting lists
    def _postings(self):
        """(post_docs, post_codes), each (S, C, dp): the base's per-shard
        posting lists, sorted from the codes on first use and cached per
        instance.  Mutations that leave the base codes alone carry the
        cache to the new index (:meth:`_carry`); the rest drop it."""
        cached = self.__dict__.get("_postings_cache")
        if cached is None:
            with annotation("repro.index.postings"), watch_region(
                    "build.postings", sig=tuple(self.codes.shape)):
                cached = _postings_program(self.codes, mesh=self.mesh)
            watch_metrics().counter("index.postings.builds").inc()
            self.__dict__["_postings_cache"] = cached
        return cached

    @property
    def post_docs(self) -> jnp.ndarray:
        """(S, C, dp) int32 doc ids, sorted by code per column and shard
        (built on first read)."""
        return self._postings()[0]

    @property
    def post_codes(self) -> jnp.ndarray:
        """(S, C, dp) the sorted codes themselves (built on first read)."""
        return self._postings()[1]

    @property
    def has_postings(self) -> bool:
        """Whether the base posting lists are built (nothing reads them
        until an engine, :attr:`max_df` or a wide-code df read asks)."""
        return "_postings_cache" in self.__dict__

    @property
    def _df_needs_postings(self) -> bool:
        """A base df read needs the posting lists only where the code range
        is too wide for a table (width 0)."""
        return not self.df_table.shape[-1]

    # --------------------------------------------------- quantized tables
    # int8 per-row copies of the dense leaves for fused_int8 phase-1.
    # Pure per-row functions of the vector bits: never persisted (store
    # commits and crash recovery re-derive identical tables), identical
    # on every mesh shape, and cached per instance like max_df.  Deletes
    # do NOT invalidate them -- tombstones only flip live/codes, and dead
    # rows are -inf-masked before quantized scores can matter -- so the
    # mutation paths carry the caches forward wherever the underlying
    # vectors leaf is shared (_carry).
    def _quant_base(self):
        """(codes (S,dp,n) int8, scale (S,dp), zero (S,dp)) of the base."""
        cached = self.__dict__.get("_quant_base_cache")
        if cached is None:
            cached = _quantize_program(self.vectors, mesh=self.mesh)
            self.__dict__["_quant_base_cache"] = cached
        return cached

    def _quant_active(self):
        """Quantized active append buffer (recomputed once per ingest
        batch -- the buffer is small and mutations return new instances)."""
        cached = self.__dict__.get("_quant_active_cache")
        if cached is None:
            cached = _quantize_program(self.seg_vectors, mesh=self.mesh)
            self.__dict__["_quant_active_cache"] = cached
        return cached

    def _carry(self, out: "ShardedVectorIndex", base: bool = False,
               active: bool = False,
               postings: bool = False) -> "ShardedVectorIndex":
        """Propagate derived caches to an index whose corresponding leaves
        are unchanged (dataclasses.replace drops them): the quant tables
        where the base / active vectors are shared, the base posting lists
        where the base codes are."""
        for flag, key in ((base, "_quant_base_cache"),
                          (active, "_quant_active_cache"),
                          (postings, "_postings_cache")):
            if flag and key in self.__dict__:
                out.__dict__[key] = self.__dict__[key]
        return out

    # -------------------------------------------------------- obs: residency
    def resident_leaves(self):
        """``(path, section, array)`` for every device-resident array this
        index holds -- the seam :func:`repro.obs.device.device_bytes` walks
        for exact byte accounting.  Crucially this includes the lazily
        derived caches (the base posting lists once built,
        ``_quant_base_cache`` / ``_quant_active_cache`` / per-segment
        ``_quant_cache``), which are real HBM residents but NOT pytree
        children, so a plain tree walk would under-report the index.  It
        never builds one: posting lists appear only once built."""
        yield "vectors", "base", self.vectors
        yield "codes", "base", self.codes
        if self.has_postings:
            yield "post_docs", "base", self.post_docs
            yield "post_codes", "base", self.post_codes
        yield "df_table", "base", self.df_table
        yield "offsets", "base", self.offsets
        yield "live", "base", self.live
        yield "seg_vectors", "active", self.seg_vectors
        yield "seg_codes", "active", self.seg_codes
        yield "seg_gids", "active", self.seg_gids
        yield "seg_live", "active", self.seg_live
        for i, seg in enumerate(self.segments):
            for nm in ("vectors", "codes", "gids", "live",
                       "post_docs", "post_codes", "df_table"):
                yield f"segments[{i}].{nm}", "segments", getattr(seg, nm)
            q = seg.__dict__.get("_quant_cache")
            if q is not None:
                for nm, arr in zip(("codes", "scale", "zero"), q):
                    yield f"segments[{i}].quant.{nm}", "quant", arr
        for key, prefix in (("_quant_base_cache", "quant.base"),
                            ("_quant_active_cache", "quant.active")):
            q = self.__dict__.get(key)
            if q is not None:
                for nm, arr in zip(("codes", "scale", "zero"), q):
                    yield f"{prefix}.{nm}", "quant", arr

    # ------------------------------------------------------------- replicas
    def replica_group(self, g: int) -> "ShardedVectorIndex":
        """View replica group ``g`` as an independent index on the 1-D
        ``data`` sub-mesh of that replica column's devices.

        Every leaf is already replicated across the ``replica`` axis, so
        each column device holds its doc-shard outright and the re-put is
        a no-copy resharding.  The group index runs the plain 1-D search
        path (bit-identical to single-device for ``page >= n_docs``) and
        can be served, searched, and compacted independently of its
        siblings -- the unit the cluster router batches per-group.  Base
        posting lists come along only if they were built."""
        R = self.n_replicas
        if not 0 <= g < R:
            raise ValueError(f"replica group must be in [0, {R}), got {g}")
        if R == 1:
            return self
        devs = np.asarray(self.mesh.devices)[:, g]
        sub = Mesh(devs, (DATA_AXIS,), axis_types=(AxisType.Auto,))
        put = lambda x, spec: jax.device_put(x, NamedSharding(sub, spec))
        out = dataclasses.replace(
            self, mesh=sub,
            vectors=put(self.vectors, _ROW),
            codes=put(self.codes, _ROW),
            df_table=put(self.df_table, _ROW),
            offsets=put(self.offsets, P(DATA_AXIS)),
            live=put(self.live, _VEC),
            seg_vectors=put(self.seg_vectors, _ROW),
            seg_codes=put(self.seg_codes, _ROW),
            seg_gids=put(self.seg_gids, _VEC),
            seg_live=put(self.seg_live, _VEC),
            segments=tuple(
                Segment(put(s.vectors, _ROW), put(s.codes, _ROW),
                        put(s.gids, _VEC), put(s.live, _VEC),
                        put(s.post_docs, _ROW), put(s.post_codes, _ROW),
                        put(s.df_table, _ROW), s.n_rows, s.tombstones)
                for s in self.segments),
        )
        if self.has_postings:
            out.__dict__["_postings_cache"] = tuple(
                put(x, _ROW) for x in self._postings())
        return out

    # -------------------------------------------------------- introspection
    def token_df(self, queries) -> jnp.ndarray:
        """Global per-token document frequencies, (Q, C) int32 -- EXACTLY
        what the query phase's idf weighting sees: per-shard df table reads
        (base and sealed generations) + the active buffer's code match,
        psum over ``data``.  With the eager
        df refresh in :meth:`delete` this counts live docs only, so
        it is invariant under :meth:`compact` -- the pin behind the
        "idf-sensitive engines score identically across compaction"
        guarantee (and a cheap cluster debugging probe)."""
        _, qcodes, _ = encode_query_rows(
            jnp.atleast_2d(jnp.asarray(queries, jnp.float32)),
            encoder=self.encoder, trim=None, best=None)
        seg = self.seg_capacity > 0
        sealed = tuple((s.post_docs, s.post_codes, s.df_table)
                       for s in self.segments)
        base = self._postings() if self._df_needs_postings else (None, None)
        return _token_df_program(
            *base, self.df_table,
            self.seg_codes if seg else None, sealed, qcodes, mesh=self.mesh,
            max_abs_bucket=self.encoder.max_abs_bucket)

    # ----------------------------------------------------------------- build
    @classmethod
    def _partition_geometry(cls, mesh: Mesh, n: int) -> Tuple[int, int, int]:
        if DATA_AXIS not in mesh.axis_names:
            raise ValueError(f"mesh has no {DATA_AXIS!r} axis: {mesh.axis_names}")
        ns = int(mesh.shape[DATA_AXIS])
        if ns > n:
            raise ValueError(f"more shards ({ns}) than documents ({n})")
        dp = math.ceil(n / ns)
        return ns, dp, ns * dp - n

    @classmethod
    def _offsets(cls, ns: int, dp: int) -> np.ndarray:
        return (np.arange(ns) * dp).astype(np.int32)

    @classmethod
    def _empty_segments(cls, mesh: Mesh, ns: int, n_feat: int, n_cols: int,
                        code_dtype):
        sentinel = _SENTINEL[np.dtype(code_dtype)]
        return (
            _put(mesh, jnp.zeros((ns, 0, n_feat), jnp.float32), _ROW),
            _put(mesh, jnp.full((ns, 0, n_cols), sentinel, code_dtype), _ROW),
            _put(mesh, jnp.full((ns, 0), -1, jnp.int32), _VEC),
            _put(mesh, jnp.zeros((ns, 0), bool), _VEC),
        )

    @classmethod
    def build_sharded(
        cls,
        vectors,
        mesh: Mesh,
        encoder: Encoder = RoundingEncoder(2),
        index_best: Optional[int] = None,
        *,
        live=None,
        seal_threshold: Optional[int] = DEFAULT_SEAL_THRESHOLD,
    ) -> "ShardedVectorIndex":
        """Build the index ON the mesh: one compiled SPMD program runs
        normalize -> encode -> ``index_best`` masking -> ``build_df_table``
        per shard under ``shard_map`` -- no per-shard host loop, no host
        round-trip (device-resident ``vectors`` are resharded in place).
        No posting list is sorted: they are built on demand.

        Bit-identical to ``VectorIndex.build(vectors, ...)`` followed by
        :meth:`from_index` (pinned by tests/test_build_parity.py): every
        stage is row-wise, so per-shard blocks produce the same bits as the
        single-device whole.  ``live=False`` rows (used by :meth:`compact`
        to carry tombstones through a rebuild) become sentinel-coded,
        zero-vector padding in place.
        """
        v = jnp.asarray(vectors)
        if v.dtype != jnp.float32:
            v = v.astype(jnp.float32)
        if v.ndim != 2:
            raise ValueError(f"vectors must be 2-D, got shape {v.shape}")
        n, n_feat = v.shape
        ns, dp, pad = cls._partition_geometry(mesh, n)
        lv = (jnp.ones((n,), bool) if live is None
              else jnp.asarray(live, bool))
        if pad:
            v = jnp.concatenate([v, jnp.zeros((pad, n_feat), jnp.float32)])
            lv = jnp.concatenate([lv, jnp.zeros((pad,), bool)])
        raw = _put(mesh, v.reshape(ns, dp, n_feat), _ROW)
        lv = _put(mesh, lv.reshape(ns, dp), _VEC)

        with watch_region("build.program",
                          sig=(int(ns), int(dp), int(n_feat))):
            vecs, codes, table = _build_program(
                raw, lv, mesh=mesh, encoder=encoder, index_best=index_best)
        _count_df_table(table)

        return cls(
            vectors=vecs,
            codes=codes,
            df_table=table,
            offsets=_put(mesh, cls._offsets(ns, dp), P(DATA_AXIS)),
            live=lv,
            encoder=encoder,
            mesh=mesh,
            n_docs=n,
            index_best=index_best,
            seal_threshold=seal_threshold,
            **cls._segments_kw(mesh, ns, n_feat, codes),
        )

    @classmethod
    def _segments_kw(cls, mesh, ns, n_feat, codes):
        sv, sc, sg, sl = cls._empty_segments(mesh, ns, n_feat,
                                             codes.shape[-1], codes.dtype)
        return {"seg_vectors": sv, "seg_codes": sc, "seg_gids": sg,
                "seg_live": sl, "segments": ()}

    @classmethod
    def from_index(cls, index: VectorIndex, mesh: Mesh, *,
                   seal_threshold: Optional[int] = DEFAULT_SEAL_THRESHOLD,
                   ) -> "ShardedVectorIndex":
        """Partition an existing single-device index across ``mesh``'s
        ``data`` axis (contiguous ranges, ES-style doc-sharding).  The
        per-shard df tables are counted in ONE compiled SPMD program (per
        shard under ``shard_map``) -- not a host loop -- and
        device-resident leaves reshard without a host numpy round-trip.

        On a ``(data, replica)`` mesh every leaf's spec leaves the
        ``replica`` axis unmentioned, so ``NamedSharding`` replicates each
        doc-shard across it -- R identical serving copies of the corpus."""
        n = index.n_docs
        ns, dp, pad = cls._partition_geometry(mesh, n)

        vectors = jnp.asarray(index.vectors)
        codes = jnp.asarray(index.codes)
        sentinel = _SENTINEL[codes.dtype]
        if pad:
            vectors = jnp.concatenate(
                [vectors, jnp.zeros((pad, vectors.shape[1]), vectors.dtype)])
            codes = jnp.concatenate(
                [codes, jnp.full((pad, codes.shape[1]), sentinel, codes.dtype)])
        n_feat = vectors.shape[1]
        vectors = _put(mesh, vectors.reshape(ns, dp, n_feat), _ROW)
        codes = _put(mesh, codes.reshape(ns, dp, -1), _ROW)

        # per-shard df tables in one SPMD program: padded docs carry the
        # sentinel and count under its entry alone
        with watch_region("build.df_table", sig=tuple(codes.shape)):
            table = _shard_df_table(codes, mesh,
                                    index.encoder.max_abs_bucket)

        offsets = cls._offsets(ns, dp)
        counts = np.clip(n - offsets, 0, dp)        # real rows per shard
        live = np.arange(dp)[None, :] < counts[:, None]
        return cls(
            vectors=vectors,
            codes=codes,
            df_table=table,
            offsets=_put(mesh, offsets, P(DATA_AXIS)),
            live=_put(mesh, live, _VEC),
            encoder=index.encoder,
            mesh=mesh,
            n_docs=n,
            index_best=index.index_best,
            seal_threshold=seal_threshold,
            **cls._segments_kw(mesh, ns, n_feat, codes),
        )

    @classmethod
    def build(cls, vectors, mesh: Mesh, encoder=None, index_best=None):
        """Build + shard in one step -- now the on-device sharded build
        (:meth:`build_sharded`); accepts device-resident vectors without a
        host numpy round-trip."""
        kwargs = {} if encoder is None else {"encoder": encoder}
        return cls.build_sharded(vectors, mesh, index_best=index_best,
                                 **kwargs)

    # ----------------------------------------------------------------- ingest
    def add_documents(self, vectors, *,
                      donate: bool = False) -> "ShardedVectorIndex":
        """Append new documents ES-style -> a new index sharing every
        unchanged leaf with ``self``.

        New docs are normalized/encoded on device, routed round-robin
        across shards, and written into per-shard append segments; global
        ids continue from ``n_ids`` (monotonic until :meth:`compact` folds
        segments into the base).  Segments are searched alongside the base
        (direct code match; no posting lists) until compaction.  Segment
        capacity grows geometrically and the query phase traces ``n_ids``
        as a runtime scalar, so an ingest stream recompiles the search
        program only O(log(appended)) times (for ``page < n_ids``), not
        per batch.

        The four active-buffer leaves update in ONE jitted program with
        explicit output shardings (no per-leaf device_put copies).  With
        ``donate=True`` the old buffers are additionally DONATED to that
        program -- zero new steady-state allocations -- which makes
        ``self`` unusable afterwards: only pass it when nothing else can
        be holding this index (the serve engine's opt-in hot-swap path
        proves that with its serving-snapshot guard).  Growth batches
        never donate: the concatenated temporaries are not committed to
        the output sharding, so XLA could not alias them anyway.
        """
        v = jnp.atleast_2d(jnp.asarray(vectors, jnp.float32))
        m = int(v.shape[0])
        if m == 0:
            return self
        if v.shape[1] != self.n_features:
            raise ValueError(
                f"expected {self.n_features}-feature vectors, got {v.shape}")
        v = normalize(v)
        codes = self.encoder.encode(v)
        sentinel = _SENTINEL[self.codes.dtype]
        if self.index_best is not None:
            codes = index_best_codes(v, codes, self.index_best, sentinel)

        ns, G = self.n_shards, self.seg_capacity
        # routing is strictly round-robin on the ACTIVE buffer's local
        # counter (n_appended - seg_base), so per-shard slot usage is a
        # pure function of the append history (tombstones keep their slot)
        # -- no device readback on the hot ingest path.  With no sealed
        # generations (seg_base == 0) this is the original global formula.
        n_act = self.n_active
        used = self._seg_slots_used(n_act, ns)
        shard_of = (n_act + np.arange(m)) % ns
        slot_of = used[shard_of] + np.arange(m) // ns
        need = int(slot_of.max()) + 1
        gids = (self.n_ids + np.arange(m)).astype(np.int32)

        svec, scod = self.seg_vectors, self.seg_codes
        sgid, sliv = self.seg_gids, self.seg_live
        grew = need > G
        if grew:
            # grow geometrically: search programs specialise on the segment
            # width, so exact-fit growth would recompile the whole SPMD
            # query phase per ingest batch -- doubling amortises that to
            # O(log(appended)) compiles (spare slots are sentinel-coded,
            # live=False, and invisible to every mask)
            grow = max(need, 2 * G, 8) - G
            n_feat, C = self.n_features, scod.shape[-1]
            svec = jnp.concatenate(
                [svec, jnp.zeros((ns, grow, n_feat), jnp.float32)], axis=1)
            scod = jnp.concatenate(
                [scod, jnp.full((ns, grow, C), sentinel, scod.dtype)], axis=1)
            sgid = jnp.concatenate(
                [sgid, jnp.full((ns, grow), -1, jnp.int32)], axis=1)
            sliv = jnp.concatenate(
                [sliv, jnp.zeros((ns, grow), bool)], axis=1)
        sh, sl = jnp.asarray(shard_of), jnp.asarray(slot_of)
        # growth batches skip donation: the concat temporaries above are
        # uncommitted, so the aliasing would be silently dropped anyway
        with watch_region("ingest.append",
                          sig=(int(m), int(svec.shape[1]), bool(grew))):
            svec, scod, sgid, sliv = _append_update(
                self.mesh, donate and not grew)(
                svec, scod, sgid, sliv, sh, sl, v,
                codes.astype(scod.dtype), jnp.asarray(gids))
        out = dataclasses.replace(
            self,
            seg_vectors=svec, seg_codes=scod, seg_gids=sgid, seg_live=sliv,
            n_appended=self.n_appended + m,
        )
        out = self._carry(out, base=True, postings=True)  # base untouched
        if (out.seal_threshold is not None
                and out.n_active >= out.seal_threshold):
            out = out._seal_active()
        return out

    def _seal_active(self) -> "ShardedVectorIndex":
        """Seal the active append buffer into an immutable :class:`Segment`.

        The buffer is truncated to its exact round-robin width, gets its
        own mini posting table and df table (the one-program SPMD builds
        :meth:`delete` and :meth:`merge_segments` use too), and joins
        ``segments``; the next :meth:`add_documents` opens a fresh active
        buffer whose geometric growth ladder restarts from empty.  A pure
        function of the op history, so translog replay re-seals at
        identical boundaries.
        """
        ns = self.n_shards
        n_act = self.n_active
        if n_act == 0:
            return self
        w = int(self._seg_slots_used(n_act, ns).max())
        svec = _put(self.mesh, self.seg_vectors[:, :w], _ROW)
        scod = _put(self.mesh, self.seg_codes[:, :w], _ROW)
        sgid = _put(self.mesh, self.seg_gids[:, :w], _VEC)
        sliv = _put(self.mesh, self.seg_live[:, :w], _VEC)
        with watch_region("ingest.seal", sig=(int(w), ns)):
            pdocs, pcodes, table = _shard_postings(
                scod, self.mesh, self.encoder.max_abs_bucket)
        seg = Segment(svec, scod, sgid, sliv, pdocs, pcodes, table,
                      n_rows=n_act, tombstones=self.active_tombstones)
        # the sealed generation inherits the active buffer's quant cache
        # as its own (same vector bits; the seal is a truncating slice, and
        # quantization is row-wise) -- but only when widths already agree,
        # else let the segment re-derive lazily
        if ("_quant_active_cache" in self.__dict__
                and self.seg_capacity == w):
            seg.__dict__["_quant_cache"] = self.__dict__[
                "_quant_active_cache"]
        ev, ec, eg, el = self._empty_segments(
            self.mesh, ns, self.n_features, self.codes.shape[-1],
            self.codes.dtype)
        out = dataclasses.replace(
            self, segments=self.segments + (seg,),
            seg_vectors=ev, seg_codes=ec, seg_gids=eg, seg_live=el,
            seg_base=self.n_appended, active_tombstones=0)
        return self._carry(out, base=True, postings=True)

    def delete(self, ids) -> "ShardedVectorIndex":
        """Tombstone documents by global id -> a new index.

        The doc's ``live`` flag goes False and its codes become the
        sentinel, so the ``codes``/``onehot`` engines skip it outright and
        the ``live`` mask blocks it from every result page.  The affected
        df tables are COUNTED AGAIN off the new codes (a tombstone counts
        only under the sentinel), so document frequencies are exact
        immediately -- idf weights, and therefore idf-sensitive phase-1
        scores, are identical before and after :meth:`compact`.  That is
        stricter than Lucene (which lets df count deleted docs until a
        merge) at the cost of one pass over the codes per delete batch -- a
        control-plane price, not a query-path one.  Base posting lists, if
        built, are dropped and sorted again when next read (the sentinel
        sorts every tombstone to the list tails); a sealed segment's mini
        posting lists are sorted again at once.
        Deleting an already-dead or padded id is a no-op for that id (and
        does not count toward ``shard_tombstones``).
        """
        ids = np.unique(np.atleast_1d(np.asarray(ids, np.int64)))
        if ids.size == 0:
            return self
        if (ids < 0).any() or (ids >= self.n_ids).any():
            raise ValueError(
                f"ids must be in [0, {self.n_ids}), got {ids.min()}..{ids.max()}")
        sentinel = _SENTINEL[self.codes.dtype]
        dead = np.zeros(self.n_shards, np.int64)
        new = {}
        base = ids[ids < self.n_docs]
        if base.size:
            s, r = np.divmod(base, self.docs_per_shard)
            was_live = np.asarray(self.live)[s, r]
            np.add.at(dead, s[was_live], 1)
            s, r = jnp.asarray(s), jnp.asarray(r)
            new["live"] = _put(self.mesh, self.live.at[s, r].set(False), _VEC)
            new["codes"] = _put(self.mesh,
                                self.codes.at[s, r].set(sentinel), _ROW)
            # exact-df refresh: the tables counted again off the updated
            # codes, where the tombstones carry the sentinel
            new["df_table"] = _shard_df_table(new["codes"], self.mesh,
                                              self.encoder.max_abs_bucket)
        app = ids[ids >= self.n_docs]
        if app.size:
            segs = list(self.segments)
            seg_changed = False
            for i, seg in enumerate(segs):
                s, g = np.nonzero(np.isin(np.asarray(seg.gids), app))
                if s.size == 0:
                    continue
                was_live = np.asarray(seg.live)[s, g]
                np.add.at(dead, s[was_live], 1)
                n_new = int(was_live.sum())
                s, g = jnp.asarray(s), jnp.asarray(g)
                codes2 = _put(self.mesh,
                              seg.codes.at[s, g].set(sentinel), _ROW)
                live2 = _put(self.mesh, seg.live.at[s, g].set(False), _VEC)
                # exact df under tombstones, per generation: rebuild the
                # segment's mini posting table and df table so its dead
                # rows count only under the sentinel
                pdocs, pcodes, table = _shard_postings(
                    codes2, self.mesh, self.encoder.max_abs_bucket)
                segs[i] = Segment(seg.vectors, codes2, seg.gids, live2,
                                  pdocs, pcodes, table, seg.n_rows,
                                  seg.tombstones + n_new)
                if "_quant_cache" in seg.__dict__:
                    # same vectors leaf; dead rows are live-masked before
                    # quantized scores matter, so the table stays valid
                    segs[i].__dict__["_quant_cache"] = \
                        seg.__dict__["_quant_cache"]
                seg_changed = True
            if seg_changed:
                new["segments"] = tuple(segs)
            s, g = np.nonzero(np.isin(np.asarray(self.seg_gids), app))
            if s.size:
                was_live = np.asarray(self.seg_live)[s, g]
                np.add.at(dead, s[was_live], 1)
                new["active_tombstones"] = (self.active_tombstones
                                            + int(was_live.sum()))
                s, g = jnp.asarray(s), jnp.asarray(g)
                new["seg_live"] = _put(
                    self.mesh, self.seg_live.at[s, g].set(False), _VEC)
                new["seg_codes"] = _put(
                    self.mesh, self.seg_codes.at[s, g].set(sentinel), _ROW)
        old = (np.asarray(self.shard_tombstones, np.int64)
               if self.shard_tombstones else np.zeros(self.n_shards, np.int64))
        new["shard_tombstones"] = tuple(int(x) for x in old + dead)
        # deletes never touch a vectors leaf -- every quant table survives;
        # the base posting lists survive where no base code changed
        return self._carry(dataclasses.replace(self, **new),
                           base=True, active=True,
                           postings="codes" not in new)

    def compact(self) -> "ShardedVectorIndex":
        """Fold append segments and tombstones back into a clean base by
        re-running the on-device sharded build over the live doc table.

        Global ids are STABLE: the new base spans ``[0, n_ids)`` in old-id
        order, with dead ids carried as sentinel-coded padding rows -- the
        df tables are counted afresh and exact; posting lists are sorted
        again only if something reads them.  The new index has
        ``n_appended == 0`` and zero-width segments.
        """
        ns, dp, n_feat = self.n_shards, self.docs_per_shard, self.n_features
        flat_v = self.vectors.reshape(ns * dp, n_feat)[: self.n_docs]
        flat_l = self.live.reshape(ns * dp)[: self.n_docs]
        if self.n_appended:
            table_v = jnp.concatenate(
                [flat_v, jnp.zeros((self.n_appended, n_feat), jnp.float32)])
            table_l = jnp.concatenate(
                [flat_l, jnp.zeros((self.n_appended,), bool)])
            parts = [(s.gids, s.vectors, s.live) for s in self.segments]
            if self.seg_capacity:
                parts.append(
                    (self.seg_gids, self.seg_vectors, self.seg_live))
            # gids are unique across generations; rows merged away stay
            # unset (live False) -- their ids were already retired
            for sgid, svec, sliv in parts:
                sg = sgid.reshape(-1)
                idx = jnp.where(sg >= 0, sg, self.n_ids)  # never-used -> OOB
                table_v = table_v.at[idx].set(
                    svec.reshape(-1, n_feat), mode="drop")
                table_l = table_l.at[idx].set(sliv.reshape(-1), mode="drop")
        else:
            table_v, table_l = flat_v, flat_l
        return type(self).build_sharded(
            table_v, self.mesh, encoder=self.encoder,
            index_best=self.index_best, live=table_l,
            seal_threshold=self.seal_threshold)

    def merge_segments(self, start: int = 0,
                       count: Optional[int] = None) -> "ShardedVectorIndex":
        """Merge a contiguous run of sealed segments into one, dropping
        tombstoned rows (Lucene's background segment merge).

        Content-preserving, not a rebuild: surviving rows keep their unit
        vectors, codes, and global ids verbatim; they are re-packed
        round-robin in id order and the merged segment gets a fresh mini
        posting table and df table.  Tombstones the run carried are
        RECLAIMED -- the per-shard ``shard_tombstones`` counters drop by
        exactly the dead rows merged away, so ``tombstone_ratio`` keeps
        meaning "deletes a compact could still fold".  Search results are
        bit-identical before and after for ``page >= n_ids``: removed rows
        were already ``-inf`` everywhere, and surviving rows keep their
        relative id order, so candidate tie-breaks cannot shift.

        Assembly is host-side gathers + ONE ``device_put`` per leaf --
        never a scatter from replica-replicated leaves (GSPMD reassembles
        such scatters with a double-counting cross-replica sum).
        """
        nseg = len(self.segments)
        if count is None:
            count = nseg - start
        if nseg == 0:
            raise ValueError("no sealed segments to merge")
        if not (0 <= start < nseg and count >= 1 and start + count <= nseg):
            raise ValueError(
                f"invalid merge range [{start}, {start + count}) "
                f"of {nseg} segments")
        run = self.segments[start:start + count]
        ns, n_feat = self.n_shards, self.n_features
        C = self.codes.shape[-1]
        sentinel = _SENTINEL[self.codes.dtype]

        keep_v, keep_c, keep_g = [], [], []
        dead_per_shard = np.zeros(ns, np.int64)
        for seg in run:
            sg = np.asarray(seg.gids)
            sl = np.asarray(seg.live)
            used = sg >= 0
            dead_per_shard += (used & ~sl).sum(axis=1)
            ks, kg = np.nonzero(used & sl)
            keep_g.append(sg[ks, kg])
            keep_v.append(np.asarray(seg.vectors)[ks, kg])
            keep_c.append(np.asarray(seg.codes)[ks, kg])
        gids = np.concatenate(keep_g)
        order = np.argsort(gids, kind="stable")   # id order = append order
        gids = gids[order]
        vecs = np.concatenate(keep_v)[order]
        codes = np.concatenate(keep_c)[order]
        n_live = int(gids.size)

        old = (np.asarray(self.shard_tombstones, np.int64)
               if self.shard_tombstones else np.zeros(ns, np.int64))
        stones = old - dead_per_shard
        stones_t = (tuple(int(x) for x in stones) if stones.any() else ())

        before, after = self.segments[:start], self.segments[start + count:]
        if n_live == 0:
            # every row in the run was dead: the generations just vanish
            return self._carry(dataclasses.replace(
                self, segments=before + after, shard_tombstones=stones_t),
                base=True, active=True, postings=True)

        w = -(-n_live // ns)
        mv = np.zeros((ns, w, n_feat), np.float32)
        mc = np.full((ns, w, C), sentinel, dtype=self.codes.dtype)
        mg = np.full((ns, w), -1, np.int32)
        ml = np.zeros((ns, w), bool)
        r = np.arange(n_live)
        sh, sl_ = r % ns, r // ns
        mv[sh, sl_] = vecs
        mc[sh, sl_] = codes
        mg[sh, sl_] = gids.astype(np.int32)
        ml[sh, sl_] = True
        dvec = _put(self.mesh, mv, _ROW)
        dcod = _put(self.mesh, mc, _ROW)
        dgid = _put(self.mesh, mg, _VEC)
        dliv = _put(self.mesh, ml, _VEC)
        with watch_region("merge.postings", sig=(int(w), ns)):
            pdocs, pcodes, table = _shard_postings(
                dcod, self.mesh, self.encoder.max_abs_bucket)
        merged = Segment(dvec, dcod, dgid, dliv, pdocs, pcodes, table,
                         n_rows=n_live, tombstones=0)
        return self._carry(dataclasses.replace(
            self, segments=before + (merged,) + after,
            shard_tombstones=stones_t), base=True, active=True, postings=True)

    # ------------------------------------------------------------------ search
    def search(
        self,
        queries: jnp.ndarray,
        k: int = 10,
        page: int = 320,
        trim: Optional[TrimFilter] = None,
        best: Optional[BestFilter] = None,
        engine: str = "postings",
        weighting: str = "idf",
        max_postings: "Optional[int | str]" = None,
        merge: str = "gather",
        live_groups: "Optional[Tuple[int, ...]]" = None,
        profile=None,
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Distributed two-phase search -> (ids (Q,k), cosine scores (Q,k)).

        Same contract as :meth:`VectorIndex.search`; bit-identical to it
        when ``page >= n_docs``, for either ``merge`` transport
        (``"gather"`` = blocking all-gather, ``"stream"`` = ring-streamed
        per-shard pages) and any replica count -- queries round-robin
        across replica groups, each holding a full copy of the corpus.
        After ingest/deletes the same protocol covers base + segments;
        result slots beyond the live doc count are ``(id=-1, score=-inf)``.

        ``max_postings="auto"`` sizes the postings window from the actual
        code distribution (:attr:`max_df`, the longest live posting list
        over every shard) -- exact like ``None``, but the window is the
        true maximum instead of the ``docs_per_shard`` worst case.

        ``live_groups`` is the failover mask: query blocks are assigned
        only to the named replica columns (dead columns get zero rows,
        which can never reach a caller) -- the health-masked merge the
        cluster control plane routes through when a group is down.

        ``profile`` is an optional :class:`~repro.obs.profile.
        ProfileNode` the phases annotate themselves into (encode,
        phase-1 with per-replica-group and per-generation candidate
        counts, merge select, rescore).  Phase boundaries are fenced
        with ``jax.block_until_ready`` -- host-side observation only,
        the computed values (and bit-parity) are untouched.
        """
        if merge not in ("gather", "stream"):
            raise ValueError(f"unknown merge transport {merge!r}")
        with annotation("repro.search.encode"):
            t_prof = time.monotonic() if profile is not None else 0.0
            R = self.n_replicas
            if live_groups is None:
                groups = tuple(range(R))
            else:
                groups = tuple(sorted({int(g) for g in live_groups}))
                if not groups or groups[0] < 0 or groups[-1] >= R:
                    raise ValueError(
                        f"live_groups must be a non-empty subset of "
                        f"[0, {R}), got {live_groups}")
            U = len(groups)
            queries = jnp.atleast_2d(queries)
            page = min(page, self.n_ids)
            k = min(k, page)
            page_loc = min(page, self.docs_per_shard + self.seg_capacity
                           + sum(s.width for s in self.segments))

            # round-robin over the LIVE replica groups: the batch splits
            # along the replica axis, so pad it to U row-blocks and place
            # block j in live column groups[j]; down columns receive zero
            # rows.  All pad and dead-column rows are dropped again below,
            # before the final rescore, and can never reach a caller.
            n_q = queries.shape[0]
            B = -(-n_q // U)                    # rows per live group
            q = jnp.asarray(queries, jnp.float32)
            pad_real = U * B - n_q
            if pad_real:
                q = jnp.concatenate(
                    [q, jnp.zeros((pad_real, q.shape[1]), jnp.float32)])
            if U < R:
                src = np.full(R * B, U * B, np.int64)       # OOB -> zero row
                for j, c in enumerate(groups):
                    src[c * B:(c + 1) * B] = np.arange(j * B, (j + 1) * B)
                zero = jnp.zeros((1, q.shape[1]), jnp.float32)
                q = jnp.concatenate([q, zero])[jnp.asarray(src)]
            q, qcodes, mask = encode_query_rows(q, encoder=self.encoder,
                                                trim=trim, best=best)
            if profile is not None:
                jax.block_until_ready((q, qcodes, mask))
                t_now = time.monotonic()
                profile.child("encode", t_now - t_prof,
                              n_queries=int(n_q), groups=U)
                t_prof = t_now

        if max_postings == "auto":
            max_postings = max(1, self.max_df)
        L = self.docs_per_shard if max_postings is None \
            else min(max_postings, self.docs_per_shard)
        seg = self.seg_capacity > 0
        sealed = tuple(
            (s.vectors, s.codes, s.gids, s.live, s.post_docs, s.post_codes,
             s.df_table)
            for s in self.segments)
        # fused_int8 scores every generation off its lazily derived int8
        # table (mixing quantized-cosine and idf-sum scales inside one
        # top_k would be meaningless); other engines pass no quant leaves
        quant = engine == "fused_int8"
        # the base posting lists go in only where something reads them: the
        # postings engine, or an idf df read with no table to read
        if engine == "postings" or (weighting == "idf" and not quant
                                    and self._df_needs_postings):
            post_docs, post_codes = self._postings()
        else:
            post_docs = post_codes = None
        with annotation("repro.search.query_phase"), watch_region(
                "search.query_phase",
                sig=(tuple(q.shape), engine, weighting, int(page_loc),
                     int(L), int(k) if merge == "stream" else 0, merge,
                     len(self.segments), bool(seg))):
            gids, scores = _query_phase(
                self.vectors, self.codes, post_docs, post_codes,
                self.df_table, self.offsets, self.live,
                self.seg_vectors if seg else None,
                self.seg_codes if seg else None,
                self.seg_gids if seg else None,
                self.seg_live if seg else None,
                sealed,
                self._quant_base() if quant else None,
                self._quant_active() if (quant and seg) else None,
                tuple(s.quantized(self.mesh) for s in self.segments)
                if quant else (),
                q, qcodes, mask, jnp.asarray(self.n_ids, jnp.int32),
                mesh=self.mesh, max_abs_bucket=self.encoder.max_abs_bucket,
                page_loc=page_loc, engine=engine, weighting=weighting,
                max_postings=L, k=k if merge == "stream" else 0, merge=merge,
            )
        # drop replica-pad and dead-column rows BEFORE the final reduce: the
        # rescore inside _merge_phase must run at the true (Q, k, n) shape
        # -- the canonical shape of exact_scores -- or pad rows would
        # perturb the einsum blocking and cost bit-parity with the
        # single-device index
        if U < R:
            sel = jnp.asarray(np.concatenate(
                [np.arange(c * B, (c + 1) * B) for c in groups])[:n_q])
            gids, scores, q = gids[sel], scores[sel], q[sel]
        elif pad_real:
            gids, scores, q = gids[:n_q], scores[:n_q], q[:n_q]
        if profile is not None:
            jax.block_until_ready((gids, scores))
            t_now = time.monotonic()
            kernel = engine if engine in FUSED_ENGINES else "composed"
            node = profile.child(
                "phase1", t_now - t_prof, engine=engine, kernel=kernel,
                page=int(page), page_loc=int(page_loc), k=int(k),
                merge=merge)
            t_prof = t_now
            # per-replica-group children: padded row-block j of the batch
            # ran on live column groups[j]
            for j, c in enumerate(groups):
                nq_j = max(0, min(n_q, (j + 1) * B) - j * B)
                if nq_j:
                    node.child(f"group{c}", n_queries=int(nq_j))
            # per-generation candidate counts, resolved host-side by gid
            # membership (profile mode only -- this is a device readback)
            gh = np.asarray(gids)
            valid = gh[gh >= 0]
            node.attrs["candidates"] = int(valid.size)
            node.child("base", rows=int(self.n_docs),
                       candidates=int((valid < self.n_docs).sum()))
            appended = valid[valid >= self.n_docs]
            for gi, s in enumerate(self.segments):
                sg = np.asarray(s.gids).ravel()
                node.child(f"gen{gi}", rows=int(s.n_rows),
                           tombstones=int(s.tombstones),
                           candidates=int(np.isin(
                               appended, sg[sg >= 0]).sum()))
            if seg and self.n_active:
                ag = np.asarray(self.seg_gids).ravel()
                node.child("active", rows=int(self.n_active),
                           tombstones=int(self.active_tombstones),
                           candidates=int(np.isin(
                               appended, ag[ag >= 0]).sum()))
        with annotation("repro.search.merge"):
            return _merge_phase(self, gids, scores, q, k=k, profile=profile)


@partial(jax.jit, static_argnames=("mesh", "encoder", "index_best"))
def _build_program(raw, live, *, mesh, encoder, index_best):
    """THE on-device build: one SPMD program, whole pipeline per shard.

    Every stage is row-wise (normalize, encode, best-mask) or
    column-independent over the local rows (the df table's counts), so
    each shard's block produces bit-identical results to the same rows
    inside a single-device build -- which is exactly the parity the
    property suite pins.  ``live=False`` rows (pads, carried tombstones)
    become zero vectors with sentinel codes, counted under the table's
    sentinel entry alone.  No posting list is sorted here.
    """
    from .shmap import shard_map

    def local(vec, lv):
        vec, lv = vec[0], lv[0]
        v = normalize(vec)
        v = jnp.where(lv[:, None], v, 0.0)
        codes = encoder.encode(v)
        sentinel = _SENTINEL[codes.dtype]
        if index_best is not None:
            codes = index_best_codes(v, codes, index_best, sentinel)
        codes = jnp.where(lv[:, None], codes,
                          jnp.asarray(sentinel, codes.dtype))
        table = build_df_table(codes, encoder.max_abs_bucket, sentinel)
        return v[None], codes[None], table[None]

    fn = shard_map(local, mesh=mesh, in_specs=(_ROW, _VEC),
                   out_specs=(_ROW,) * 3, check=False)
    return fn(raw, live)


@functools.lru_cache(maxsize=None)
def _append_update(mesh: Mesh, donate: bool):
    """The fused append-update program for the ingest hot path.

    All four active-buffer leaves scatter-update in ONE jitted program
    with explicit output shardings -- replacing four eager ``.at[].set``
    + ``device_put`` pairs (eight buffer allocations per batch) with a
    single XLA computation (four allocations, or ZERO with donation:
    ``donate=True`` aliases each input buffer to its output, so the
    update happens in place).  Scatter targets here are the data-sharded
    seg leaves, never anything replica-replicated-only, so the GSPMD
    scatter hazard (see merge_segments) does not apply.  Cached per
    (mesh, donate); jit caches per batch shape inside.
    """
    row = NamedSharding(mesh, _ROW)
    vec = NamedSharding(mesh, _VEC)

    def upd(svec, scod, sgid, sliv, sh, sl, v, c, g):
        return (svec.at[sh, sl].set(v),
                scod.at[sh, sl].set(c),
                sgid.at[sh, sl].set(g),
                sliv.at[sh, sl].set(True))

    return jax.jit(upd,
                   donate_argnums=(0, 1, 2, 3) if donate else (),
                   out_shardings=(row, row, vec, vec))


@partial(jax.jit, static_argnames=("mesh",))
def _quantize_program(vectors, *, mesh):
    """Per-shard int8 row quantization in one SPMD program: (S, W, n) f32
    -> (codes (S, W, n) int8, scale (S, W), zero (S, W)).  Row-wise, so
    per-shard blocks quantize to the same bits as the rows would anywhere
    else -- mesh shape and generation layout can't change a code."""
    from .shmap import shard_map

    def local(v):
        q8, sc, zp = quantize_rows(v[0])
        return q8[None], sc[None], zp[None]

    fn = shard_map(local, mesh=mesh, in_specs=(_ROW,),
                   out_specs=(_ROW, _VEC, _VEC), check=False)
    return fn(vectors)


@partial(jax.jit, static_argnames=("mesh",))
def _postings_program(codes, *, mesh):
    """Per-shard posting lists in one SPMD program: (post_docs,
    post_codes), each (S, C, W), one stable argsort per shard."""
    from .shmap import shard_map

    def local(c):
        p = build_postings(c[0])
        return p.post_docs[None], p.post_codes[None]

    fn = shard_map(local, mesh=mesh, in_specs=(_ROW,),
                   out_specs=(_ROW, _ROW), check=False)
    return fn(codes)


@partial(jax.jit, static_argnames=("mesh", "max_abs_bucket"))
def _df_table_program(codes, *, mesh, max_abs_bucket):
    """Per-shard df tables counted off the codes in one SPMD program:
    (S, C, W) int32."""
    from .shmap import shard_map

    sentinel = int(_SENTINEL[codes.dtype])

    def local(c):
        return build_df_table(c[0], max_abs_bucket, sentinel)[None]

    fn = shard_map(local, mesh=mesh, in_specs=(_ROW,), out_specs=_ROW,
                   check=False)
    return fn(codes)


def _shard_df_table(codes, mesh: Mesh, max_abs_bucket: int):
    """The df table of every shard of ``codes`` (S, W, C), counted."""
    table = _df_table_program(codes, mesh=mesh,
                              max_abs_bucket=max_abs_bucket)
    _count_df_table(table)
    return table


def _shard_postings(codes, mesh: Mesh, max_abs_bucket: int):
    """(post_docs, post_codes, df_table) of every shard of ``codes``
    (S, W, C): a sealed segment's mini posting lists and its df table."""
    pdocs, pcodes = _postings_program(codes, mesh=mesh)
    return pdocs, pcodes, _shard_df_table(codes, mesh, max_abs_bucket)


def _count_df_table(table) -> None:
    """Count one (re)build of an index's df tables -- build, delete's
    exact-df refresh, seal, merge, restore -- in ``index.df_table.builds``
    of the registry this thread's watch regions report to.  Searches only
    read tables, so they never count; an empty table (code range too wide)
    is not a table."""
    if table.shape[-1]:
        watch_metrics().counter("index.df_table.builds").inc()


def _merge_phase(sidx, gids, scores, q, *, k, profile=None):
    """Coordinating-node reduce: global top-k over the exact cosines, then
    final scores recomputed at the (Q, k, n) shape shared with rerank_topk
    -- see exact_scores for why this gives bit-parity.  For the stream
    transport the inputs are already the merged (Q, k) page (sorted by
    score), so the top-k is an identity pass and only the rescore runs.

    The select + candidate-vector fetch run distributed (top-k and gather
    are exact, layout can't change a bit); the rescore einsum runs on the
    coordinating device with *unsharded* operands, because GSPMD blocks a
    sharded einsum differently per mesh shape -- rescoring in-mesh costs
    last-ulp parity between e.g. a 4x1 and a 2x4 layout of the same corpus.

    Result slots whose merged score is -inf (fewer than k live candidates)
    report id -1 and keep score -inf through the rescore.
    """
    t_prof = time.monotonic() if profile is not None else 0.0
    seg_parts = tuple((s.vectors, s.gids) for s in sidx.segments)
    if sidx.n_appended and sidx.seg_capacity:
        seg_parts += ((sidx.seg_vectors, sidx.seg_gids),)
    with watch_region("search.merge_select",
                      sig=(tuple(gids.shape), int(k), len(seg_parts))):
        if seg_parts:
            top_ids, cvec = _merge_select_seg(
                sidx.vectors, seg_parts, gids, scores, k=k,
                n_docs=sidx.n_docs)
        else:
            # no appended rows anywhere (fresh index, or every appended
            # row was merged away dead): candidates are base gids only
            top_ids, cvec = _merge_select(sidx.vectors, gids, scores, k=k)
    if profile is not None:
        jax.block_until_ready((top_ids, cvec))
        t_now = time.monotonic()
        profile.child("merge_select", t_now - t_prof, k=int(k),
                      generations=len(seg_parts))
        t_prof = t_now
    dev = jax.devices()[0]
    cvec_d = jax.device_put(cvec, dev)
    q_d = jax.device_put(q, dev)
    ids_d = jax.device_put(top_ids, dev)
    with watch_region("search.rescore", sig=(tuple(q.shape), int(k))):
        out = _rescore(cvec_d, q_d, ids_d)
    if profile is not None:
        jax.block_until_ready(out)
        profile.child("rescore", time.monotonic() - t_prof, k=int(k))
    return top_ids, out


@partial(jax.jit, static_argnames=("k",))
@jax.named_scope("merge_select")
def _merge_select(vectors, gids, scores, *, k):
    top_s, pos = jax.lax.top_k(scores, k)
    top_ids = jnp.take_along_axis(gids, pos, axis=1)
    top_ids = jnp.where(jnp.isneginf(top_s), -1, top_ids)
    flat_vectors = vectors.reshape(-1, vectors.shape[-1])
    cvec = flat_vectors[jnp.maximum(top_ids, 0)]    # (Q, k, n) hit vectors
    return top_ids, cvec


@partial(jax.jit, static_argnames=("k", "n_docs"))
@jax.named_scope("merge_select")
def _merge_select_seg(vectors, seg_parts, gids, scores, *, k, n_docs):
    """Merge select over base + appended generations.

    ``seg_parts`` is a tuple of ``(vectors (S, G, n), gids (S, G))`` pairs
    -- the sealed segments plus the active buffer.  Pure gathers only (no
    scatter): base hits fetch from the flat base by gid = flat row;
    appended hits (gid >= ``n_docs``) resolve their slot by gid equality
    within each generation (gids are unique across generations) and fold
    in with a ``where``.  Scatter-built lookup tables are unsafe here --
    on a replicated ``(data, replica)`` layout GSPMD reassembles a
    scattered table with a cross-replica sum that double-counts the base
    rows.  The fold is PER generation on purpose: concatenating two
    generations' (data-sharded, replica-replicated) leaves and gathering
    from the concatenation miscompiles the same way on a replica mesh
    (the gathered row comes back as a cross-replica combination that
    matches no source row), while single-layout gathers stay exact.
    """
    top_s, pos = jax.lax.top_k(scores, k)
    top_ids = jnp.take_along_axis(gids, pos, axis=1)
    top_ids = jnp.where(jnp.isneginf(top_s), -1, top_ids)
    n_feat = vectors.shape[-1]
    flat = vectors.reshape(-1, n_feat)              # rows [0, S*dp)
    cvec = flat[jnp.clip(top_ids, 0, flat.shape[0] - 1)]
    for v, g in seg_parts:
        sg = g.reshape(-1)
        sv = v.reshape(-1, n_feat)
        match = top_ids[:, :, None] == sg[None, None, :]
        slot = jnp.argmax(match, axis=-1)
        found = match.any(axis=-1)
        cvec = jnp.where(found[..., None], sv[slot], cvec)
    return top_ids, cvec                            # (Q, k, n) hit vectors


@jax.jit
@jax.named_scope("rescore")
def _rescore(cvec, q, top_ids):
    """exact_scores' canonical (Q, k, n) einsum over pre-fetched hits;
    unfillable (id -1) slots stay -inf instead of a junk-row cosine."""
    s = jnp.einsum("qkn,qn->qk", cvec, q,
                   preferred_element_type=jnp.float32, precision=EXACT)
    return jnp.where(top_ids < 0, -jnp.inf, s)


@partial(jax.jit, static_argnames=("mesh", "max_abs_bucket", "page_loc",
                                   "engine", "weighting", "max_postings",
                                   "k", "merge"))
def _query_phase(vectors, codes, post_docs, post_codes, df_table, offsets,
                 live, seg_vectors, seg_codes, seg_gids, seg_live, sealed,
                 base_quant, act_quant, sealed_quant,
                 q, qcodes, mask, n_ids, *, mesh, max_abs_bucket, page_loc,
                 engine, weighting, max_postings, k, merge):
    """Per-shard query phase under shard_map -> merge-ready candidates.

    ``merge="gather"``: returns global candidate ids (Q, S*page_loc) and
    their exact cosine scores (one all-gather; padded/invalid candidates
    are ``-inf``).  ``merge="stream"``: candidate pages ring-rotate along
    the ``data`` axis and fold into a running top-``k`` in shard order on
    each group's coordinator, which then broadcasts -- returns the merged
    (Q, k) ids/scores directly.  On a ``(data, replica)`` mesh the query
    batch additionally splits along ``replica`` (Q/R rows per group) and
    reassembles in the out-spec.

    Under ``weighting="idf"`` (scope ``df_lookup``) each shard reads its
    tokens' df from its ``df_table`` in one pass (``table_df``, a
    compare-and-select over the codes the table holds, integer-identical to
    a posting-range lookup); a shard whose code range is too wide for a
    table (width 0) runs that lookup per token instead.  ``post_docs`` /
    ``post_codes`` are ``None`` unless the engine (``postings``) or that
    lookup reads them.

    Appended docs live in generations: ``sealed`` is a tuple of
    ``(vectors, codes, gids, live, post_docs, post_codes, df_table)``
    leaf-tuples -- one per sealed :class:`Segment` -- and ``seg_*`` is the
    active append buffer (``None`` when empty).  Every generation scores by
    direct per-column bucket equality (the identity every engine lowers
    to, which is what pins bit-parity with the flat path), but *df* comes
    from each sealed segment's own df table (integer-exact and equal to
    the dense count) while the active buffer still uses ``code_df``.
    Candidate order is base, then generations oldest-first, then the
    active buffer -- per shard that is exactly append order, the same
    tie-break order as the flat buffer, so ``top_k`` stability makes the
    candidate pages match the pre-generational program bit for bit.

    Takes leaves, not the index pytree, and the id-space size ``n_ids`` as
    a TRACED scalar: repeated ingest batches that stay within the segment
    capacity then hit this jit's cache (same shapes, same treedef) instead
    of recompiling the SPMD program per ``add_documents``; seals and
    merges change the treedef and recompile O(maintenance events) times.

    The ``fused``/``fused_int8`` engines replace the dense-scores +
    ``top_k`` pair with the fused kernel's streamed selection over the
    BASE (top ``min(page_loc, dp)`` of the base always covers every base
    candidate the composed top-k could pick), then one top-k over [base
    page | generation scores] in the same concat-index space -- identical
    candidates, same downstream gather/rescore.  ``fused_int8`` scores
    every generation off the per-row int8 tables (``*_quant`` args,
    ``None``/empty for other engines) and reads no tokens, so the idf
    psum is skipped entirely.
    """
    from .shmap import shard_map

    dp = vectors.shape[1]
    G = 0 if seg_vectors is None else seg_vectors.shape[1]
    n_shards = vectors.shape[0]
    n_sealed = len(sealed)
    widths = tuple(t[0].shape[1] for t in sealed)
    quant = engine == "fused_int8"
    has_post = post_docs is not None

    sentinel = int(_SENTINEL[codes.dtype])

    def local(*args):
        vec, codes, dft, off, lv = args[:5]
        rest = args[5:]
        postings = None
        if has_post:
            postings = Postings(rest[0][0], rest[1][0], dp)
            rest = rest[2:]
        if G:
            svec, scod, sgid, sliv = (x[0] for x in rest[:4])
            rest = rest[4:]
        segs = [tuple(x[0] for x in rest[i * 7:(i + 1) * 7])
                for i in range(n_sealed)]
        rest = rest[n_sealed * 7:]
        if quant:
            bq8, bsc, bzp = (x[0] for x in rest[:3])
            rest = rest[3:]
            if G:
                aq8, asc, azp = (x[0] for x in rest[:3])
                rest = rest[3:]
            seg_quants = [tuple(x[0] for x in rest[i * 3:(i + 1) * 3])
                          for i in range(n_sealed)]
            rest = rest[n_sealed * 3:]
        q, qcodes, mask, n_ids = rest
        vec, codes, lv = vec[0], codes[0], lv[0]
        off = off[0]

        if quant:
            w = None    # token-free engine: no df psum, no idf weights
        elif weighting == "idf":
            with jax.named_scope("df_lookup"):
                df = table_df(dft[0], postings, qcodes, max_abs_bucket,
                              sentinel)
                for i, (_, _, _, _, spd, spc, sdt) in enumerate(segs):
                    # sealed generations read their own df tables:
                    # integer-equal to the dense code_df count
                    df = df + table_df(sdt, Postings(spd, spc, widths[i]),
                                       qcodes, max_abs_bucket, sentinel)
                if G:
                    df = df + code_df(scod, qcodes)
            with jax.named_scope("idf_psum"):
                df = jax.lax.psum(df, DATA_AXIS)    # global df, exact
                w = idf_weights(df, n_ids)
        elif weighting == "count":
            w = jnp.ones(qcodes.shape, jnp.float32)
        else:
            raise ValueError(f"unknown weighting {weighting!r}")
        if w is not None:
            w = jnp.where(mask, w, 0.0)

        def seg_scores(sc, sl):
            # generation phase 1: direct bucket-equality match (the
            # identity every engine lowers); sentinel slots never match
            # but mask them anyway -- liveness must not hinge on codes
            eq = (qcodes[:, None, :] == sc[None, :, :]).astype(jnp.int8)
            s_seg = jnp.einsum("qgc,qc->qg", eq, w,
                               preferred_element_type=jnp.float32)
            return jnp.where(sl[None, :], s_seg, -jnp.inf)

        def seg_scores_fused(sc, sl):
            # the fused branch scores generations with the SAME ordered
            # column fold the kernel uses for the base (ref.match_scores),
            # so every doc's phase-1 bits are identical across the seg and
            # flat layouts -- the einsum form above reduces in a
            # shape-dependent order and would wobble the last ulp
            from repro.kernels.fused_phase1.ref import match_scores

            return jnp.where(sl[None, :], match_scores(sc, qcodes, w),
                             -jnp.inf)

        def seg_scores_quant(t, sl):
            # generation phase 1 under fused_int8: the same per-row
            # affine-int8 score the base kernel computes -- quantization
            # is row-wise, so a row scores identically in a sealed
            # generation and in the flat buffer (the parity pin)
            s8, ssc, szp = t
            raw = jnp.einsum("qn,gn->qg", q, s8.astype(jnp.float32),
                             preferred_element_type=jnp.float32)
            s_seg = raw * ssc[None, :] + qsum * szp[None, :]
            return jnp.where(sl[None, :], s_seg, -jnp.inf)

        with jax.named_scope("phase1"):
            if engine in FUSED_ENGINES:
                # fused selection: the kernel streams the base and returns
                # its top min(page_loc, dp) directly -- a superset of every
                # base candidate the composed top-k could select -- then ONE
                # top-k merges it with the (small) generation scores in the
                # same concat-index space [base | sealed... | active] the
                # composed path uses.  Stable top-k order matches the
                # composed concat (base entries keep ascending-id tie order
                # and precede generation entries), so `cand` is identical
                # wherever scores are finite; -inf slots differ only in
                # unspecified ids, which the live mask turns into (id=-1,
                # -inf) either way.
                from repro.kernels.fused_phase1 import ops as fp_ops

                p_base = min(page_loc, dp)
                if quant:
                    qsum = jnp.sum(q, axis=-1, keepdims=True)
                    s_b, ids_b = fp_ops.fused_phase1_quant(
                        bq8, bsc, bzp, q, page=p_base, live=lv)
                else:
                    s_b, ids_b = fp_ops.fused_phase1(
                        codes, qcodes, w, page=p_base, live=lv)
                parts_s, parts_i = [s_b], [ids_b]
                gen_off = dp
                gen_sc = ([seg_scores_quant(seg_quants[i], segs[i][3])
                           for i in range(n_sealed)] if quant else
                          [seg_scores_fused(segs[i][1], segs[i][3])
                           for i in range(n_sealed)])
                for i, sc_i in enumerate(gen_sc):
                    parts_s.append(sc_i)
                    parts_i.append(gen_off + jax.lax.broadcasted_iota(
                        jnp.int32, sc_i.shape, 1))
                    gen_off += widths[i]
                if G:
                    sc_a = (seg_scores_quant((aq8, asc, azp), sliv) if quant
                            else seg_scores_fused(scod, sliv))
                    parts_s.append(sc_a)
                    parts_i.append(gen_off + jax.lax.broadcasted_iota(
                        jnp.int32, sc_a.shape, 1))
                if len(parts_s) == 1:
                    cand = ids_b                        # p_base == page_loc
                else:
                    cat_s = jnp.concatenate(parts_s, axis=1)
                    cat_i = jnp.concatenate(parts_i, axis=1)
                    _, pos = jax.lax.top_k(cat_s, page_loc)
                    cand = jnp.take_along_axis(cat_i, pos, axis=1)
            else:
                s1 = phase1_engine_scores(codes, postings, qcodes, w,
                                          engine, max_postings,
                                          max_abs_bucket)
                # pads/tombstones out
                s1 = jnp.where(lv[None, :], s1, -jnp.inf)
                parts = [s1]
                parts += [seg_scores(sc_, sl_)
                          for _, sc_, _, sl_, _, _, _ in segs]
                if G:
                    parts.append(seg_scores(scod, sliv))
                s1 = (parts[0] if len(parts) == 1
                      else jnp.concatenate(parts, axis=1))
                _, cand = jax.lax.top_k(s1, page_loc)   # (Q, page_loc)

        with jax.named_scope("shard_rescore"):
            if segs or G:
                vparts = [vec] + [t[0] for t in segs]
                lparts = [lv] + [t[3] for t in segs]
                gparts = ([off + jnp.arange(dp, dtype=jnp.int32)]
                          + [t[2] for t in segs])
                if G:
                    vparts.append(svec)
                    lparts.append(sliv)
                    gparts.append(sgid)
                vec_all = jnp.concatenate(vparts, axis=0)
                live_all = jnp.concatenate(lparts)
                gid_all = jnp.concatenate(gparts)
            else:
                vec_all, live_all = vec, lv
            cvec = vec_all[cand]                        # (Q, page_loc, n)
            s2 = jnp.einsum("qpn,qn->qp", cvec, q,
                            preferred_element_type=jnp.float32,
                            precision=EXACT)
            s2 = jnp.where(live_all[cand], s2, -jnp.inf)
            gid = (gid_all[cand] if (segs or G)
                   else (cand + off).astype(jnp.int32))
        if merge == "gather":
            return gid, s2
        with jax.named_scope("merge_select"):
            return _stream_merge_local(gid, s2, n_shards, k)

    rep = REPLICA_AXIS in mesh.axis_names
    qaxis = REPLICA_AXIS if rep else None
    args = [vectors, codes, df_table, offsets, live]
    specs = [_ROW, _ROW, _ROW, P(DATA_AXIS), _VEC]
    if has_post:
        args += [post_docs, post_codes]
        specs += [_ROW, _ROW]
    if G:
        args += [seg_vectors, seg_codes, seg_gids, seg_live]
        specs += [_ROW, _ROW, _VEC, _VEC]
    for leaves in sealed:
        args += list(leaves)
        specs += [_ROW, _ROW, _VEC, _VEC, _ROW, _ROW, _ROW]
    if quant:
        args += list(base_quant)
        specs += [_ROW, _VEC, _VEC]
        if G:
            args += list(act_quant)
            specs += [_ROW, _VEC, _VEC]
        for t in sealed_quant:
            args += list(t)
            specs += [_ROW, _VEC, _VEC]
    args += [q, qcodes, mask, n_ids]
    specs += [P(qaxis, None)] * 3 + [P()]
    out = P(qaxis, DATA_AXIS) if merge == "gather" else P(qaxis, None)
    fn = shard_map(
        local, mesh=mesh,
        in_specs=tuple(specs),
        out_specs=(out, out),
        check=False,
    )
    return fn(*args)


def _stream_merge_local(gid, s2, n_shards, k):
    """Ring-streamed coordinator merge (runs inside the shard_map body).

    Pages rotate shard -> shard-1 along ``data``; after step t the device
    at data index i holds the page of shard (i+t) % S, so the group
    coordinator (data index 0) folds pages in shard order 0..S-1 -- the
    same shard-major tie-break order as the flat all-gather, which is what
    keeps the two transports bit-identical.  Each fold is a (k+page)-wide
    stable top-k, so communication of the next page overlaps the fold of
    the current one and peak memory stays k+page per query instead of
    S*page.  The coordinator's result is broadcast with a masked psum
    (every other device contributes zeros).

    Pre-merge ``-inf`` placeholder rows surface only when fewer than ``k``
    live candidates exist across the S pages (possible after deletes);
    the merge select downstream reports those slots as (id=-1, -inf).
    """
    acc_s = jnp.full((s2.shape[0], k), -jnp.inf, s2.dtype)
    acc_i = jnp.zeros((gid.shape[0], k), gid.dtype)
    perm = [(j, (j - 1) % n_shards) for j in range(n_shards)]
    for t in range(n_shards):
        cat_s = jnp.concatenate([acc_s, s2], axis=1)
        cat_i = jnp.concatenate([acc_i, gid], axis=1)
        acc_s, pos = jax.lax.top_k(cat_s, k)
        acc_i = jnp.take_along_axis(cat_i, pos, axis=1)
        if t < n_shards - 1:
            s2 = jax.lax.ppermute(s2, DATA_AXIS, perm)
            gid = jax.lax.ppermute(gid, DATA_AXIS, perm)
    lead = jax.lax.axis_index(DATA_AXIS) == 0
    acc_i = jax.lax.psum(jnp.where(lead, acc_i, 0), DATA_AXIS)
    acc_s = jax.lax.psum(jnp.where(lead, acc_s, 0.0), DATA_AXIS)
    return acc_i, acc_s


@partial(jax.jit, static_argnames=("mesh", "sentinel"))
def _max_df_program(post_codes, *, mesh, sentinel):
    """Longest live posting list over every (shard, column) -> scalar.

    Per shard the posting codes are already sorted per column, so a run of
    equal values IS a posting list: segment-count the runs, read each
    position's run length back, mask the sentinel tail, and pmax across
    shards.  This is the exact ``max_postings`` window -- every legal
    posting range fits -- computed from the shard's real code
    distribution instead of the ``docs_per_shard`` worst case.
    """
    from .shmap import shard_map

    d = post_codes.shape[-1]

    def local(pc):
        x = pc[0]                                   # (C, d) sorted rows

        def run_max(row):
            change = jnp.concatenate(
                [jnp.zeros((1,), jnp.int32),
                 (row[1:] != row[:-1]).astype(jnp.int32)])
            gid = jnp.cumsum(change)
            counts = jax.ops.segment_sum(
                jnp.ones((d,), jnp.int32), gid, num_segments=d)
            return jnp.max(jnp.where(row != sentinel, counts[gid], 0))

        return jax.lax.pmax(jnp.max(jax.vmap(run_max)(x)), DATA_AXIS)

    fn = shard_map(local, mesh=mesh, in_specs=(_ROW,), out_specs=P(),
                   check=False)
    return fn(post_codes)


@partial(jax.jit, static_argnames=("mesh", "max_abs_bucket"))
def _token_df_program(post_docs, post_codes, df_table, seg_codes, sealed,
                      qcodes, *, mesh, max_abs_bucket):
    """Global per-token df, the query phase's idf input verbatim: per-shard
    df table read (``table_df``: base + each sealed generation's own
    table) plus the active buffer's code match, psum over ``data``.
    ``sealed`` is a tuple of (post_docs, post_codes, df_table) triples; the
    base's ``post_docs`` / ``post_codes`` are ``None`` where its table is
    read.  Queries are replicated (df is identical in every replica
    group)."""
    from .shmap import shard_map

    G = seg_codes is not None
    has_post = post_docs is not None
    sentinel = int(_SENTINEL[qcodes.dtype])

    def local(*args):
        *leaves, qc = args
        if G:
            *leaves, sc = leaves
        leaves = [x[0] for x in leaves]
        base = None
        if has_post:
            pd, pc, *leaves = leaves
            base = Postings(pd, pc, pc.shape[-1])
        df = table_df(leaves[0], base, qc, max_abs_bucket, sentinel)
        for i in range(1, len(leaves), 3):      # sealed triples
            pd, pc, dt = leaves[i:i + 3]
            df = df + table_df(dt, Postings(pd, pc, pc.shape[-1]), qc,
                               max_abs_bucket, sentinel)
        if G:
            df = df + code_df(sc[0], qc)
        return jax.lax.psum(df, DATA_AXIS)

    args = ([post_docs, post_codes] if has_post else []) + [df_table]
    for leaves in sealed:
        args += list(leaves)
    args += [seg_codes] if G else []
    specs = [_ROW] * len(args)
    args += [qcodes]
    specs += [P(None, None)]
    fn = shard_map(local, mesh=mesh, in_specs=tuple(specs),
                   out_specs=P(None, None), check=False)
    return fn(*args)
