"""Commit points: content-addressed incremental snapshots of the index.

The Lucene side of durability, now with the ES *incremental snapshot*
model.  A commit point is a generation-numbered manifest
(``commit-<gen>.json``) whose atomic rename IS the commit -- a crash
mid-write leaves no manifest, so the previous commit stays authoritative
-- plus a set of **content-addressed blob files** the manifest references:

* ``seg-<digest>.seg`` -- one deterministic RSEG container per index
  *part*: the base vectors, the base search state (codes + live), the
  active append buffer, and one blob per sealed
  :class:`~repro.dist.shard_index.Segment`.  The file name is a digest of
  the blob bytes, so a part whose content did not change since the last
  commit hashes to the SAME file and is simply *referenced again* instead
  of rewritten -- commits are O(changed parts), not O(index), exactly how
  an ES snapshot reuses unchanged Lucene segment files across snapshots.
  Determinism is why ``np.savez`` is NOT used here: zipfile stamps
  timestamps into member headers, so equal arrays would produce unequal
  bytes and break the content addressing.  RSEG is magic + a
  ``sort_keys`` JSON array directory + raw C-order array bytes: equal
  arrays <=> equal bytes.
* ``commit-<gen>.json`` -- the manifest: translog seqno covered,
  geometry + segment metadata, encoder parameters, and per-blob
  ``{file, crc32, bytes}`` entries.  :func:`latest_commit` walks
  generations newest-first and returns the first whose manifest AND every
  referenced blob checksum verify, so a torn newest commit falls back to
  the previous one.

**Retention + GC**: :func:`write_commit` keeps the newest two manifests
(current + fallback, so a torn newest data file can still recover) and
then deletes every ``seg-*.seg`` not referenced by ANY retained manifest.
The GC set is the union over retained manifests -- a blob the fallback
commit still references is never deleted, however old.  Callers that
interleave GC with recovery (the :class:`~repro.store.durable.Store`)
serialize both on one lock, so a restore in progress can never have a
referenced blob unlinked under it.

:func:`restore` rebuilds a device-resident :class:`ShardedVectorIndex`:

* host-numpy assembly + ONE ``device_put`` per leaf -- **scatter-free by
  construction**.  This matters on a ``(data, replica)`` mesh: building a
  device table with scatter (``.at[].set``) from replica-replicated
  operands makes GSPMD reassemble the scatter with a cross-replica sum
  that double-counts rows (the ``_merge_select_seg`` gotcha, see
  ROADMAP).
* on the writer's own shard count every stored leaf restores
  bit-identically (blobs hold the per-shard layouts verbatim).  On a
  different shard count, rows re-place by the same deterministic rules
  ingest/merge used: active rows by their append offset
  (``gid - n_docs - seg_base``), sealed-segment rows by gid rank,
  round-robin -- search parity at ``page >= n_ids`` holds on any mesh.
* per-shard df tables (base and per segment) and the segments' mini
  posting lists are rebuilt with the same one-program SPMD programs the
  live index uses, so they are bit-identical to the committed index's on
  the same mesh shape.  Base posting lists are never stored: the restored
  index, like a fresh build, sorts them only when something reads them.

``shard_tombstones`` is exact on a same-shard-count restore; restoring to
a different shard count redistributes the writer's TOTAL round-robin
(per-shard deletion history is advisory maintenance pressure, not search
state -- the live masks and sentinel codes in the snapshot are the search
truth and restore exactly).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import math
import os
import re
import struct
import zlib
from typing import Optional

import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.encoding import (CombinedEncoder, Encoder, IntervalEncoder,
                                 RoundingEncoder)
from repro.core.search import _SENTINEL
from repro.dist.shard_index import (Segment, ShardedVectorIndex,
                                    _put, _ROW, _shard_df_table,
                                    _shard_postings, _VEC)
from repro.dist.sharding import DATA_AXIS

__all__ = ["CommitPoint", "write_commit", "latest_commit", "restore",
           "encoder_meta", "encoder_from_meta"]

_FORMAT_VERSION = 2
_MANIFEST_RE = re.compile(r"^commit-(\d{8})\.json$")
_BLOB_RE = re.compile(r"^seg-[0-9a-f]{16}\.seg$")
_BLOB_MAGIC = b"RSEG"
_RETAINED_COMMITS = 2      # current + one fallback (ES keeps the previous
#                            segments_N for exactly this torn-file case)


# --------------------------------------------------------- encoder (de)ser
def encoder_meta(enc: Encoder) -> dict:
    if isinstance(enc, RoundingEncoder):
        return {"type": "rounding", "precision": enc.precision}
    if isinstance(enc, IntervalEncoder):
        return {"type": "interval", "width": enc.width}
    if isinstance(enc, CombinedEncoder):
        return {"type": "combined", "rounding": encoder_meta(enc.rounding),
                "interval": encoder_meta(enc.interval)}
    raise TypeError(f"cannot serialize encoder {type(enc).__name__}")


def encoder_from_meta(meta: dict) -> Encoder:
    kind = meta.get("type")
    if kind == "rounding":
        return RoundingEncoder(int(meta["precision"]))
    if kind == "interval":
        return IntervalEncoder(float(meta["width"]))
    if kind == "combined":
        return CombinedEncoder(encoder_from_meta(meta["rounding"]),
                               encoder_from_meta(meta["interval"]))
    raise ValueError(f"unknown encoder meta {meta!r}")


# ------------------------------------------------------------ fs plumbing
from .translog import _fsync_dir  # noqa: E402 - one dirent-durability impl


def _write_atomic(path: str, data: bytes) -> None:
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(data)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    _fsync_dir(os.path.dirname(path))


def _crc32_file(path: str, chunk: int = 1 << 20) -> int:
    """Streaming crc32 -- the snapshot can be the whole corpus, so never
    pull it into memory just to checksum it."""
    crc = 0
    with open(path, "rb") as f:
        while True:
            block = f.read(chunk)
            if not block:
                return crc
            crc = zlib.crc32(block, crc)


def _manifest_path(store_dir: str, gen: int) -> str:
    return os.path.join(store_dir, f"commit-{gen:08d}.json")


def _list_commits(store_dir: str):
    gens = []
    for name in os.listdir(store_dir):
        m = _MANIFEST_RE.match(name)
        if m:
            gens.append(int(m.group(1)))
    return sorted(gens)


# ------------------------------------------------------ RSEG blob container
def _pack_blob(arrays: dict) -> bytes:
    """Named numpy arrays -> one deterministic byte string.

    Layout: ``RSEG`` magic, little-endian u32 header length, a
    ``sort_keys``/no-whitespace JSON directory of ``{name, dtype, shape}``
    entries (insertion order preserved -- it indexes the payload), then
    each array's raw C-order bytes.  No timestamps, no compression, no
    alignment padding: equal arrays produce equal bytes, which is the
    whole content-addressing contract.
    """
    entries, payload = [], []
    for name, arr in arrays.items():
        a = np.ascontiguousarray(arr)
        entries.append({"name": name, "dtype": np.dtype(a.dtype).str,
                        "shape": list(a.shape)})
        payload.append(a.tobytes())
    header = json.dumps({"version": 1, "arrays": entries}, sort_keys=True,
                        separators=(",", ":")).encode()
    return b"".join([_BLOB_MAGIC, struct.pack("<I", len(header)), header]
                    + payload)


def _unpack_blob(path: str) -> dict:
    with open(path, "rb") as f:
        blob = f.read()
    if blob[:4] != _BLOB_MAGIC:
        raise ValueError(f"{path!r} is not an RSEG blob")
    (hlen,) = struct.unpack("<I", blob[4:8])
    directory = json.loads(blob[8:8 + hlen])
    out, off = {}, 8 + hlen
    for e in directory["arrays"]:
        dt, shape = np.dtype(e["dtype"]), tuple(e["shape"])
        n = dt.itemsize * int(np.prod(shape, dtype=np.int64))
        out[e["name"]] = np.frombuffer(
            blob, dtype=dt, count=int(np.prod(shape, dtype=np.int64)),
            offset=off).reshape(shape)
        off += n
    return out


def _write_blob(store_dir: str, arrays: dict, stats: dict) -> dict:
    """Write (or re-reference) one content-addressed blob -> its manifest
    entry.  An existing file with the same digest name and byte length IS
    this content (digest collisions at equal length are out of scope for
    a 128-bit truncated sha256) -- the write is skipped and only
    ``bytes_total`` grows, which is the entire sharing mechanism."""
    blob = _pack_blob(arrays)
    name = f"seg-{hashlib.sha256(blob).hexdigest()[:16]}.seg"
    path = os.path.join(store_dir, name)
    stats["bytes_total"] += len(blob)
    if not (os.path.exists(path) and os.path.getsize(path) == len(blob)):
        _write_atomic(path, blob)
        stats["bytes_written"] += len(blob)
        stats["blobs_written"] += 1
    return {"file": name, "crc32": zlib.crc32(blob), "bytes": len(blob)}


def _referenced_blobs(meta: dict) -> set:
    files = meta.get("files", {})
    refs = {e["file"] for k, e in files.items()
            if k != "segments" and e is not None}
    refs.update(e["file"] for e in files.get("segments", ()))
    return refs


@dataclasses.dataclass(frozen=True)
class CommitPoint:
    """One verified commit: manifest dict + the store directory holding
    the content-addressed blobs it references."""

    generation: int
    seq: int
    meta: dict
    data_path: str            # the store directory


# ----------------------------------------------------------------- commit
def write_commit(store_dir: str, index: ShardedVectorIndex, seq: int,
                 stats: Optional[dict] = None) -> int:
    """Snapshot ``index`` as the next commit generation covering translog
    seqno ``seq``; returns the generation number.

    Every blob lands (fsync'd, or is already on disk from an earlier
    generation -- the content-addressed sharing) before the manifest, and
    the manifest rename is the commit: interrupted writes are invisible to
    :func:`latest_commit`.  Cost is O(changed parts): the base vectors
    blob rewrites only after a compact, the base state only after base
    deletes, a sealed segment's blob only after deletes hit it, and the
    active-buffer blob per append batch -- unchanged parts re-reference
    their existing file.  ``stats`` (optional dict) receives
    ``bytes_written`` / ``bytes_total`` / ``blobs_written`` for the
    benchmarks that measure the O(changed) claim instead of asserting it.
    """
    os.makedirs(store_dir, exist_ok=True)
    ns, dp = index.n_shards, index.docs_per_shard
    nf, n_docs = index.n_features, index.n_docs
    n_act = index.n_active
    if stats is None:
        stats = {}
    stats.update(bytes_written=0, bytes_total=0, blobs_written=0)

    files = {
        "base_vectors": _write_blob(store_dir, {
            "vectors": np.asarray(index.vectors).reshape(ns * dp, nf)
            [:n_docs]}, stats),
        "base_state": _write_blob(store_dir, {
            "codes": np.asarray(index.codes).reshape(ns * dp, -1)[:n_docs],
            "live": np.asarray(index.live).reshape(ns * dp)[:n_docs],
        }, stats),
        "active": None,
        "segments": [],
    }
    if n_act:
        j = np.arange(n_act)
        sg = np.asarray(index.seg_gids)
        if not np.array_equal(sg[j % ns, j // ns],
                              n_docs + index.seg_base + j):
            raise ValueError(
                "active-buffer gids violate round-robin routing -- "
                "refusing to write a snapshot that would not restore "
                "bit-identically")
        # the FULL (S, G) leaves, spare sentinel slots included: a
        # same-mesh restore then reproduces the leaf bits exactly, and
        # the blob only changes when the buffer content does
        files["active"] = _write_blob(store_dir, {
            "vectors": np.asarray(index.seg_vectors),
            "codes": np.asarray(index.seg_codes),
            "gids": sg,
            "live": np.asarray(index.seg_live),
        }, stats)
    for s in index.segments:
        entry = _write_blob(store_dir, {
            "vectors": np.asarray(s.vectors),
            "codes": np.asarray(s.codes),
            "gids": np.asarray(s.gids),
            "live": np.asarray(s.live),
        }, stats)
        entry.update(n_rows=s.n_rows, tombstones=s.tombstones)
        files["segments"].append(entry)

    gens = _list_commits(store_dir)
    gen = (gens[-1] + 1) if gens else 1
    manifest = {
        "format_version": _FORMAT_VERSION,
        "generation": gen,
        "seq": int(seq),
        "n_docs": n_docs,
        "n_appended": index.n_appended,
        "seg_base": index.seg_base,
        "active_tombstones": index.active_tombstones,
        "n_features": nf,
        "code_columns": int(index.codes.shape[-1]),
        "writer_shards": ns,
        "seal_threshold": index.seal_threshold,
        "seg_capacity": index.seg_capacity,
        "shard_tombstones": [int(t) for t in (index.shard_tombstones
                                              or (0,) * ns)],
        "index_best": index.index_best,
        "encoder": encoder_meta(index.encoder),
        "files": files,
        "bytes_written": stats["bytes_written"],
        "bytes_total": stats["bytes_total"],
    }
    _write_atomic(_manifest_path(store_dir, gen),
                  json.dumps(manifest, indent=1).encode())
    _gc_commits(store_dir)
    return gen


def _gc_commits(store_dir: str) -> None:
    """Retention + blob GC: keep the newest ``_RETAINED_COMMITS``
    manifests, then delete every ``seg-*.seg`` no retained manifest
    references.

    The live set is the UNION over retained manifests -- a blob shared
    with (or only referenced by) the fallback commit survives, however
    many generations ago it was written.  A retained manifest that fails
    to parse contributes nothing to the live set but also aborts the
    sweep: deleting blobs while a manifest is unreadable could strand the
    one commit recovery will fall back to.  Callers racing recovery must
    hold the store lock around the whole commit (``Store.commit`` does) --
    that is the GC-safety contract for in-progress ``restore_group``.
    """
    gens = _list_commits(store_dir)
    for old in gens[:-_RETAINED_COMMITS]:
        try:
            os.remove(_manifest_path(store_dir, old))
        except OSError:
            pass
    live: set = set()
    for gen in gens[-_RETAINED_COMMITS:]:
        try:
            with open(_manifest_path(store_dir, gen)) as f:
                live |= _referenced_blobs(json.load(f))
        except (OSError, ValueError):
            return                       # unreadable manifest: skip the GC
    for name in os.listdir(store_dir):
        if _BLOB_RE.match(name) and name not in live:
            try:
                os.remove(os.path.join(store_dir, name))
            except OSError:
                pass


def latest_commit(store_dir: str, *,
                  validate: bool = True) -> Optional[CommitPoint]:
    """Newest commit whose manifest parses AND (with ``validate``, the
    default) whose referenced blobs all match their checksums; earlier
    generations are the fallback (ES keeps the previous ``segments_N``
    for exactly this reason).  None if no valid commit.
    ``validate=False`` skips the per-blob CRCs -- for seq-only lookups
    (e.g. the commit retention bookkeeping) where a full-corpus read per
    call would be pure waste."""
    if not os.path.isdir(store_dir):
        return None
    for gen in reversed(_list_commits(store_dir)):
        try:
            with open(_manifest_path(store_dir, gen)) as f:
                meta = json.load(f)
            if meta.get("format_version") != _FORMAT_VERSION:
                continue
            entries = ([meta["files"][k] for k in ("base_vectors",
                                                   "base_state", "active")
                        if meta["files"][k] is not None]
                       + list(meta["files"]["segments"]))
            ok = True
            for e in entries:
                path = os.path.join(store_dir, e["file"])
                if validate:
                    ok = (os.path.getsize(path) == e["bytes"]
                          and _crc32_file(path) == e["crc32"])
                else:
                    ok = os.path.exists(path)
                if not ok:
                    break
            if not ok:
                continue
        except (OSError, ValueError, KeyError):
            continue
        return CommitPoint(generation=gen, seq=int(meta["seq"]), meta=meta,
                           data_path=store_dir)
    return None


# ---------------------------------------------------------------- restore
def restore(commit: CommitPoint, mesh: Mesh) -> ShardedVectorIndex:
    """Rebuild a device-resident index from ``commit`` on ``mesh``.

    On the writer's own shard count the stored per-shard layouts reload
    verbatim, so every leaf is bit-identical to the committed index's.  A
    different shard count re-places rows host-side by the deterministic
    rules ingest/merge used (active rows by append offset, sealed rows by
    gid rank, round-robin) and places each leaf with one ``device_put``
    (scatter-free -- see module docstring for the replica-mesh GSPMD
    gotcha); df tables (base and per segment) are counted again and the
    segments' mini posting lists sorted again by the same SPMD programs
    the live paths use.  The base posting lists are not stored: like a
    fresh build, the restored index sorts them only if something reads
    them.  On any shape, search results match at ``page >= n_ids``.
    """
    meta = commit.meta
    store_dir = commit.data_path
    files = meta["files"]
    blob = lambda entry: _unpack_blob(os.path.join(store_dir, entry["file"]))
    base_vectors = blob(files["base_vectors"])["vectors"]
    base_state = blob(files["base_state"])
    base_codes, base_live = base_state["codes"], base_state["live"]

    n_docs, n_app = int(meta["n_docs"]), int(meta["n_appended"])
    seg_base = int(meta["seg_base"])
    n_act = n_app - seg_base
    nf, C = int(meta["n_features"]), int(meta["code_columns"])
    encoder = encoder_from_meta(meta["encoder"])
    cdtype = base_codes.dtype
    sentinel = _SENTINEL[jnp.dtype(cdtype)]
    ns, dp, pad = ShardedVectorIndex._partition_geometry(mesh, n_docs)
    same_shards = ns == int(meta["writer_shards"])

    vec = np.zeros((ns * dp, nf), np.float32)
    vec[:n_docs] = base_vectors
    codes = np.full((ns * dp, C), sentinel, cdtype)
    codes[:n_docs] = base_codes
    live = np.zeros((ns * dp,), bool)
    live[:n_docs] = base_live

    vectors = _put(mesh, vec.reshape(ns, dp, nf), _ROW)
    codes = _put(mesh, codes.reshape(ns, dp, C), _ROW)
    live = _put(mesh, live.reshape(ns, dp), _VEC)
    table = _shard_df_table(codes, mesh, encoder.max_abs_bucket)

    # ----- active append buffer
    if files["active"] is not None and same_shards:
        act = blob(files["active"])        # leaf-level bit-identity
        sv, sc = act["vectors"], act["codes"]
        sg, sl = act["gids"], act["live"]
    else:
        if n_act:
            act = blob(files["active"])
            # a fresh geometric ladder, as one add_documents from empty
            # would allocate; spare slots are sentinel-coded and invisible
            cap = max(math.ceil(n_act / ns), 8)
        else:
            cap = 0
        sv = np.zeros((ns, cap, nf), np.float32)
        sc = np.full((ns, cap, C), sentinel, cdtype)
        sg = np.full((ns, cap), -1, np.int32)
        sl = np.zeros((ns, cap), bool)
        if n_act:
            rows = act["gids"].reshape(-1) >= 0
            gids = act["gids"].reshape(-1)[rows]
            # active rows re-place by append offset: the j-th doc appended
            # since the last seal sits in slot j // S of shard j % S
            j = gids - n_docs - seg_base
            s, g = j % ns, j // ns
            sv[s, g] = act["vectors"].reshape(-1, nf)[rows]
            sc[s, g] = act["codes"].reshape(-1, C)[rows]
            sg[s, g] = gids.astype(np.int32)
            sl[s, g] = act["live"].reshape(-1)[rows]

    # ----- sealed segments
    segments = []
    for e in files["segments"]:
        part = blob(e)
        if same_shards:
            mv, mc = part["vectors"], part["codes"]
            mg, ml = part["gids"], part["live"]
        else:
            rows = part["gids"].reshape(-1) >= 0
            gids = part["gids"].reshape(-1)[rows]
            order = np.argsort(gids, kind="stable")
            # sealed rows re-place by gid rank -- the rule both sealing
            # (contiguous gids) and merging (id-order re-pack) produce
            w = -(-int(e["n_rows"]) // ns)
            mv = np.zeros((ns, w, nf), np.float32)
            mc = np.full((ns, w, C), sentinel, cdtype)
            mg = np.full((ns, w), -1, np.int32)
            ml = np.zeros((ns, w), bool)
            r = np.arange(gids.size)
            s, g = r % ns, r // ns
            mv[s, g] = part["vectors"].reshape(-1, nf)[rows][order]
            mc[s, g] = part["codes"].reshape(-1, C)[rows][order]
            mg[s, g] = gids[order].astype(np.int32)
            ml[s, g] = part["live"].reshape(-1)[rows][order]
        dcod = _put(mesh, mc, _ROW)
        spd, spc, sdt = _shard_postings(dcod, mesh, encoder.max_abs_bucket)
        segments.append(Segment(
            _put(mesh, mv, _ROW), dcod, _put(mesh, mg, _VEC),
            _put(mesh, ml, _VEC), spd, spc, sdt,
            n_rows=int(e["n_rows"]), tombstones=int(e["tombstones"])))

    stones = [int(t) for t in meta["shard_tombstones"]]
    if not same_shards:
        total = sum(stones)                 # advisory: exact total, even
        stones = [total // ns + (i < total % ns) for i in range(ns)]
    if not any(stones):
        stones = []                         # the fresh-index spelling

    seal = meta["seal_threshold"]
    return ShardedVectorIndex(
        vectors=vectors,
        codes=codes,
        df_table=table,
        offsets=_put(mesh, ShardedVectorIndex._offsets(ns, dp),
                     P(DATA_AXIS)),
        live=live,
        seg_vectors=_put(mesh, sv, _ROW),
        seg_codes=_put(mesh, sc, _ROW),
        seg_gids=_put(mesh, sg, _VEC),
        seg_live=_put(mesh, sl, _VEC),
        segments=tuple(segments),
        encoder=encoder,
        mesh=mesh,
        n_docs=n_docs,
        index_best=meta["index_best"],
        n_appended=n_app,
        shard_tombstones=tuple(stones),
        seal_threshold=None if seal is None else int(seal),
        seg_base=seg_base,
        active_tombstones=int(meta["active_tombstones"]),
    )
