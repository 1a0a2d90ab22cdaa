"""Content-addressed blob storage (CAS): chunk-level dedup + cache.

The reserved ``cas/`` storage_id is a generic **content-addressed blob
store** with two clients:

- **checkpoint chunks** (``cas/chunks/``): ``CASStorageManager`` sits
  between ``CheckpointContext`` and any concrete
  :class:`~determined_clone_tpu.storage.base.StorageManager` backend. It
  splits checkpoint payload files into fixed-size chunks keyed by their
  sha256, stores each chunk once, and writes a per-checkpoint **chunk
  manifest** alongside PR 4's ``manifest.json``/``COMMIT`` protocol
  files. Successive checkpoints (and different trials sharing a storage
  root) re-upload only the chunks that actually changed — the
  incremental-checkpoint result of Check-N-Run (NSDI '22) / CheckFreq
  (FAST '21), see docs/checkpoint_storage.md.
- **spilled KV blocks** (``cas/kv/``): :class:`KVBlobStore` is the
  durable tier of the fleet KV memory hierarchy (serving/kv_store.py)
  — exact K/V block payloads keyed by the prefix cache's chained
  content hash, so a restarted or replacement replica warms shared
  prefixes by *fetching* instead of re-prefilling (docs/serving.md).

Both ride the same :class:`BlobService` transport — digest-keyed object
paths, sha256 verification on every read, local :class:`ChunkCache`
read-through, fault-point injection — so the integrity and chaos
machinery proven on checkpoints applies to spilled blocks unchanged.

Protocol extension: a checkpoint is restorable iff its COMMIT marker
exists (unchanged from PR 4) AND every chunk its manifests reference
exists in the chunk namespace and digest-verifies. A torn or missing
chunk surfaces as :class:`CheckpointCorruptError`, which the trainer's
restore-fallback walk already handles (training/trainer.py:_restore).

Restores are read-through: chunks are served from a local size-capped LRU
:class:`ChunkCache` (digest-verified on every hit) and only fetched from
the backend on a miss — a warm restart or a corrupt-newest fallback walk
re-downloads nothing it already has.

All bulk transfers fan out over the shared bounded
:class:`~determined_clone_tpu.storage.transfer.TransferPool`; per-chunk
retries use the storage retry policy; ``cas.chunk_upload`` /
``cas.chunk_drop`` / ``cas.chunk_download`` fault points make torn-chunk
and lost-chunk failures injectable (docs/fault_tolerance.md).
"""
from __future__ import annotations

import contextlib
import hashlib
import json
import logging
import os
import pickle
import shutil
import tempfile
import threading
import time
import uuid
from typing import Any, Dict, Iterable, List, Optional, Set, Tuple

from determined_clone_tpu import faults
from determined_clone_tpu.storage import transfer
from determined_clone_tpu.storage.base import (
    COMMIT_FILE,
    StorageManager,
    _transfer,
    _walk_relative,
)

logger = logging.getLogger(__name__)

# Reserved storage_id holding the shared blob objects (checkpoint chunks
# AND spilled KV blocks); never a checkpoint. GC sweeps and
# list_storage_ids() must skip it.
CHUNK_NAMESPACE = "cas"

# Blob namespaces inside the reserved storage_id. Chunk GC only ever
# deletes ``chunks/...`` rels (structurally — see BlobService.rel), so
# ``kv/...`` entries can never be swept as orphan chunks; their
# lifecycle is the namespace's budget sweep (:func:`sweep_namespace`)
# instead.
CHUNK_PREFIX = "chunks"
KV_BLOB_PREFIX = "kv/blobs"
KV_INDEX_PREFIX = "kv/index"

# Per-upload-call chunk manifest written into the checkpoint's namespace.
# One file per upload() call (so sharded ranks never collide); restore
# merges every cas-manifest-*.json it finds.
CHUNK_MANIFEST_PREFIX = "cas-manifest-"

# Files stored verbatim in the checkpoint namespace: the commit-protocol
# files must stay directly readable (validate/bootstrap), and chunking
# them would gain nothing.
_PASSTHROUGH_FILES = ("manifest.json", "metadata.json", COMMIT_FILE)

DEFAULT_CHUNK_SIZE = 1 << 20  # 1 MiB
DEFAULT_CACHE_BYTES = 256 << 20


def _is_chunk_manifest(rel: str) -> bool:
    return rel.startswith(CHUNK_MANIFEST_PREFIX) and rel.endswith(".json")


def _is_passthrough(rel: str) -> bool:
    return rel in _PASSTHROUGH_FILES or _is_chunk_manifest(rel)


def chunk_rel(digest: str) -> str:
    """Backend-relative object path of a chunk (fan out by digest prefix
    so shared_fs directories stay enumerable)."""
    return f"chunks/{digest[:2]}/{digest}"


def _digest_of_rel(rel: str) -> Optional[str]:
    parts = rel.split("/")
    if len(parts) == 3 and parts[0] == "chunks" and len(parts[2]) == 64:
        return parts[2]
    return None


def _sha256_bytes(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()


def _sha256_file(path: str, block: int = 1 << 20) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for piece in iter(lambda: f.read(block), b""):
            h.update(piece)
    return h.hexdigest()


def _corrupt(storage_id: str, reason: str) -> Exception:
    # lazy import: core._checkpoint imports storage.base; importing it at
    # module top from inside the storage package would be circular
    from determined_clone_tpu.core._checkpoint import CheckpointCorruptError

    return CheckpointCorruptError(storage_id, reason)


class ChunkCache:
    """Local on-disk LRU chunk cache, keyed by sha256, size-capped.

    Every hit is digest-verified before it is served — a corrupted cache
    entry is silently discarded and counts as a miss, so the cache can
    never launder bad bytes into a restore. Hit/miss counters persist in
    ``stats.json`` (flushed every :data:`FLUSH_EVERY` lookups and on every
    ``stats()`` call, not per-lookup — restores fetch thousands of chunks
    and must not pay a file write each) so ``dct checkpoint stats`` can
    report the hit rate across processes. Recency is tracked via file
    mtimes (touched on every hit), which survives process restarts.

    Two processes may share a cache_path (trainer + ``dct checkpoint
    stats``, or neighboring trials on one host): every filesystem
    operation here tolerates entries vanishing underneath it, treating a
    foreign eviction as a plain miss.
    """

    FLUSH_EVERY = 64

    def __init__(self, path: str,
                 max_bytes: int = DEFAULT_CACHE_BYTES) -> None:
        if max_bytes < 1:
            raise ValueError(f"cache max_bytes must be >= 1, got {max_bytes}")
        self.path = path
        self.max_bytes = max_bytes
        self._dir = os.path.join(path, "chunks")
        self._stats_path = os.path.join(path, "stats.json")
        self._lock = threading.RLock()
        os.makedirs(self._dir, exist_ok=True)
        self._stats = {"hits": 0, "misses": 0}
        self._unflushed = 0
        if os.path.exists(self._stats_path):
            try:
                with open(self._stats_path) as f:
                    doc = json.load(f)
                self._stats["hits"] = int(doc.get("hits", 0))
                self._stats["misses"] = int(doc.get("misses", 0))
            except (ValueError, OSError):
                pass  # unreadable stats file: counters restart at zero

    def _entry(self, digest: str) -> str:
        return os.path.join(self._dir, digest)

    def _flush_stats(self) -> None:
        self._unflushed = 0
        try:
            tmp = self._stats_path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(self._stats, f)
            os.replace(tmp, self._stats_path)
        except OSError:
            pass  # a cache that cannot persist counters must not fail I/O

    def _note(self, key: str) -> None:
        self._stats[key] += 1
        self._unflushed += 1
        if self._unflushed >= self.FLUSH_EVERY:
            self._flush_stats()

    def get(self, digest: str) -> Optional[str]:
        """Path of the verified cached chunk, or None (counted as a miss)."""
        with self._lock:
            p = self._entry(digest)
            try:
                if os.path.exists(p) and _sha256_file(p) == digest:
                    os.utime(p)  # LRU touch
                    self._note("hits")
                    return p
                if os.path.exists(p):
                    # digest mismatch: a torn cache write or bit rot — evict
                    # so the next restore re-fetches the real bytes
                    os.remove(p)
            except FileNotFoundError:
                pass  # another process evicted it mid-check: a miss
            self._note("misses")
            return None

    def put(self, digest: str, data: bytes) -> str:
        with self._lock:
            p = self._entry(digest)
            with contextlib.suppress(FileNotFoundError):
                if os.path.exists(p):
                    os.utime(p)
                    return p
            fd, tmp = tempfile.mkstemp(dir=self._dir, prefix=".put-")
            try:
                with os.fdopen(fd, "wb") as f:
                    f.write(data)
                os.replace(tmp, p)
            finally:
                if os.path.exists(tmp):
                    os.remove(tmp)
            self._evict(keep=digest)
            return p

    def _evict(self, keep: str) -> None:
        entries = []
        for name in os.listdir(self._dir):
            ep = os.path.join(self._dir, name)
            try:
                if os.path.isfile(ep) and not name.startswith("."):
                    entries.append((os.path.getmtime(ep),
                                    os.path.getsize(ep), name, ep))
            except FileNotFoundError:
                pass  # vanished between listdir and stat (shared cache)
        total = sum(e[1] for e in entries)
        # oldest-first, but never the entry just written (a cache smaller
        # than one chunk would otherwise thrash forever)
        for _, size, name, ep in sorted(entries):
            if total <= self.max_bytes:
                return
            if name == keep:
                continue
            with contextlib.suppress(FileNotFoundError):
                os.remove(ep)
            total -= size

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            sizes = []
            for n in os.listdir(self._dir):
                p = os.path.join(self._dir, n)
                try:
                    if not n.startswith(".") and os.path.isfile(p):
                        sizes.append(os.path.getsize(p))
                except FileNotFoundError:
                    pass  # vanished between listdir and stat (shared cache)
            self._flush_stats()  # make the durable counters current
            hits, misses = self._stats["hits"], self._stats["misses"]
            looked = hits + misses
            return {
                "path": self.path,
                "entries": len(sizes),
                "bytes": sum(sizes),
                "max_bytes": self.max_bytes,
                "hits": hits,
                "misses": misses,
                "hit_rate": round(hits / looked, 4) if looked else None,
            }


class BlobIntegrityError(Exception):
    """A blob is missing from the store or fails digest verification."""

    def __init__(self, digest: str, reason: str, *,
                 missing: bool = False) -> None:
        super().__init__(f"blob {digest[:12]}…: {reason}")
        self.digest = digest
        self.reason = reason
        self.missing = missing


class BlobService:
    """Digest-keyed blob transport over the reserved ``cas`` storage_id.

    One instance per namespace — checkpoint chunks under ``chunks/``,
    spilled KV blocks under ``kv/blobs/`` — each with its own
    fault-point names so chaos tests can tear or drop either object kind
    independently. Shared guarantees:

    - objects live at ``<prefix>/<digest[:2]>/<digest>`` (fanned out so
      shared_fs directories stay enumerable);
    - every read is sha256-verified against its key before it is served
      (:class:`BlobIntegrityError` on mismatch — a torn object can never
      launder bad bytes into a restore or a promoted KV block);
    - an optional local :class:`ChunkCache` serves repeat reads without
      touching the backend (itself digest-verified per hit);
    - ``fault_store`` / ``fault_drop`` / ``fault_load`` name the
      injection points (faults/core.py) for torn writes, lost objects,
      and failed reads.

    The ``counter`` hook receives ``(key, n)`` accounting events
    (``cache_hits`` / ``cache_misses`` / ``bytes_downloaded``) so the
    owning manager can fold them into its session stats and metrics.
    """

    def __init__(self, inner: StorageManager, prefix: str = CHUNK_PREFIX, *,
                 cache: Optional[ChunkCache] = None,
                 fault_store: Optional[str] = None,
                 fault_drop: Optional[str] = None,
                 fault_load: Optional[str] = None,
                 counter: Optional[Any] = None) -> None:
        self._inner = inner
        self.prefix = prefix
        self._cache = cache
        self._fault_store = fault_store
        self._fault_drop = fault_drop
        self._fault_load = fault_load
        self._count = counter if counter is not None else (lambda k, n: None)

    def rel(self, digest: str) -> str:
        """Backend-relative object path of a blob."""
        return f"{self.prefix}/{digest[:2]}/{digest}"

    def digest_of_rel(self, rel: str) -> Optional[str]:
        """Inverse of :meth:`rel`; None for anything outside this
        namespace (another namespace's blobs, index files, strays)."""
        head = self.prefix + "/"
        if not rel.startswith(head):
            return None
        parts = rel[len(head):].split("/")
        if (len(parts) == 2 and len(parts[1]) == 64
                and parts[0] == parts[1][:2]):
            return parts[1]
        return None

    def list_blobs(self) -> Dict[str, int]:
        """digest -> size for every blob in this namespace RIGHT NOW
        (fresh backend listing, no memo)."""
        listing = self._inner.list_files(CHUNK_NAMESPACE)
        out: Dict[str, int] = {}
        for rel, size in listing.items():
            d = self.digest_of_rel(rel)
            if d is not None:
                out[d] = int(size)
        return out

    def put(self, data: bytes, *, digest: Optional[str] = None
            ) -> Optional[str]:
        """Store bytes under their sha256 (or a caller-supplied digest —
        the chunk path already hashed during scan). Returns the digest,
        or None when an injected drop swallowed the object (the caller
        decides whether that is fatal)."""
        if digest is None:
            digest = _sha256_bytes(data)
        if self._fault_store is not None:
            faults.point(self._fault_store)
        if (self._fault_drop is not None
                and faults.truncate_bytes(self._fault_drop) is not None):
            return None
        rel = self.rel(digest)
        with tempfile.TemporaryDirectory(prefix="dct-blob-up-") as stage:
            staged = os.path.join(stage, rel)
            os.makedirs(os.path.dirname(staged), exist_ok=True)
            with open(staged, "wb") as f:
                f.write(data)
            if self._fault_store is not None:
                keep = faults.truncate_bytes(self._fault_store)
                if keep is not None:
                    # injected torn object: truncated bytes land under the
                    # full digest's key — read-side digest-verify convicts
                    with open(staged, "r+b") as f:
                        f.truncate(keep)
            self._inner.upload(stage, CHUNK_NAMESPACE, paths=[rel])
        if self._cache is not None:
            self._cache.put(digest, data)
        return digest

    def get(self, digest: str) -> bytes:
        """Fetch + digest-verify one blob (cache first, then backend).
        Raises :class:`BlobIntegrityError` when missing or torn."""
        if self._fault_load is not None:
            faults.point(self._fault_load)
        if self._cache is not None:
            hit = self._cache.get(digest)
            if hit is not None:
                self._count("cache_hits", 1)
                with open(hit, "rb") as f:
                    return f.read()
            self._count("cache_misses", 1)
        rel = self.rel(digest)
        with tempfile.TemporaryDirectory(prefix="dct-blob-dl-") as tmp:
            try:
                self._inner.download(CHUNK_NAMESPACE, tmp, paths=[rel])
                with open(os.path.join(tmp, rel), "rb") as f:
                    data = f.read()
            except (FileNotFoundError, KeyError):
                raise BlobIntegrityError(
                    digest, "missing from the blob store",
                    missing=True) from None
        if _sha256_bytes(data) != digest:
            raise BlobIntegrityError(
                digest, "content digest mismatch (torn blob)")
        self._count("bytes_downloaded", len(data))
        if self._cache is not None:
            self._cache.put(digest, data)
        return data

    def delete(self, digests: Iterable[str]) -> None:
        self._inner.delete_files(
            CHUNK_NAMESPACE, [self.rel(d) for d in sorted(digests)])


def namespace_usage(inner: StorageManager, namespace: str) -> Dict[str, int]:
    """rel -> size for every object (blobs AND index files) under one
    blob namespace (``kv``) of the reserved ``cas`` storage_id."""
    head = namespace.rstrip("/") + "/"
    try:
        listing = inner.list_files(CHUNK_NAMESPACE)
    except (FileNotFoundError, KeyError):
        return {}
    return {rel: int(size) for rel, size in listing.items()
            if rel.startswith(head)}


def sweep_namespace(inner: StorageManager, namespace: str,
                    budget_bytes: int) -> Dict[str, Any]:
    """LRU-by-mtime byte-budget sweep for one blob namespace; the
    eviction path of ``cas/kv/``.

    Deletes the oldest objects (by backend mtime, via the optional
    ``file_mtimes`` capability) until the namespace fits its budget.
    Objects are evicted individually — an index whose blob got swept
    (or vice versa) is harmless, because the namespace's client
    (:class:`KVBlobStore`) treats ANY load failure as a plain miss and
    re-creates the pair on the next store.
    Backends that cannot stat mtimes or delete per-object skip the
    sweep gracefully (``swept: False``). Chunk GC never touches the
    namespace (structurally — see the CHUNK_PREFIX note), so this
    sweep is its only eviction path.
    """
    usage = namespace_usage(inner, namespace)
    total = sum(usage.values())
    out: Dict[str, Any] = {"namespace": namespace, "swept": True,
                           "budget_bytes": int(budget_bytes),
                           "evicted": 0, "evicted_bytes": 0,
                           "bytes": total}
    if total <= budget_bytes:
        return out
    try:
        mtimes = inner.file_mtimes(CHUNK_NAMESPACE, sorted(usage))
    except NotImplementedError:
        out["swept"] = False
        return out
    # oldest first; objects the backend could not stat sort first (age
    # unknown — most likely vanished already, deleting them is a no-op)
    order = sorted(usage, key=lambda rel: (mtimes.get(rel, 0.0), rel))
    doomed: List[str] = []
    for rel in order:
        if total <= budget_bytes:
            break
        doomed.append(rel)
        total -= usage[rel]
        out["evicted"] += 1
        out["evicted_bytes"] += usage[rel]
    if doomed:
        try:
            inner.delete_files(CHUNK_NAMESPACE, doomed)
        except NotImplementedError:
            return {**out, "swept": False, "evicted": 0,
                    "evicted_bytes": 0, "bytes": sum(usage.values())}
        logger.info("cas namespace sweep: %s evicted %d objects "
                    "(%d bytes) to fit %d-byte budget",
                    namespace, out["evicted"], out["evicted_bytes"],
                    budget_bytes)
    out["bytes"] = total
    return out


class KVBlobStore:
    """CAS tier of the fleet KV memory hierarchy (serving/kv_store.py).

    Third (durable, cross-process) level of the device → host → CAS
    hierarchy: exact K/V block payloads spilled by any replica land
    under ``cas/kv/`` and can warm a restarted or replacement replica
    in another process. The layout is a
    content-addressed pickle blob under ``kv/blobs/`` plus one small
    JSON index record per chain key under ``kv/index/`` — so the same
    integrity machinery applies: every blob read is sha256-verified,
    the pickled payload carries its key for a final cross-check, and
    EVERY failure mode (missing index, torn blob, foreign-blob index,
    unpickling error, injected fault) degrades to a *plain miss*. The
    engine then re-prefills, so the tier can only ever serve exact
    bytes or nothing — which is what keeps greedy decoding
    bit-identical (docs/serving.md).

    ``kv_store.spill`` / ``kv_store.fetch`` fault points fire here
    (docs/fault_tolerance.md); torn spills are injected by truncating
    the staged blob under its full digest's key, so the fetch-side
    digest check convicts.
    """

    def __init__(self, inner: StorageManager, *,
                 budget_bytes: Optional[int] = None,
                 sweep_every: int = 32) -> None:
        self._inner = inner
        self._blobs = BlobService(inner, KV_BLOB_PREFIX)
        self.budget_bytes = budget_bytes
        self.sweep_every = max(1, int(sweep_every))
        self._lock = threading.Lock()
        self._since_sweep = 0
        self.session: Dict[str, int] = {
            "hits": 0, "misses": 0, "stores": 0, "duplicate_stores": 0,
            "errors": 0, "evictions": 0,
            "bytes_stored": 0, "bytes_loaded": 0,
        }

    @staticmethod
    def key_digest(key: Dict[str, str]) -> str:
        """Stable digest of a tier key (params fingerprint + chain
        hash); names the index record."""
        return _sha256_bytes(
            json.dumps(key, sort_keys=True).encode("utf-8"))

    @staticmethod
    def _index_rel(key_digest: str) -> str:
        return f"{KV_INDEX_PREFIX}/{key_digest}.json"

    def _note(self, key: str, n: int = 1) -> None:
        with self._lock:
            self.session[key] += n

    def _read_index(self, key_digest: str) -> Optional[Dict[str, Any]]:
        rel = self._index_rel(key_digest)
        with tempfile.TemporaryDirectory(prefix="dct-kv-idx-") as tmp:
            try:
                self._inner.download(CHUNK_NAMESPACE, tmp, paths=[rel])
                with open(os.path.join(tmp, rel)) as f:
                    return json.load(f)
            except (FileNotFoundError, KeyError, ValueError, OSError):
                return None

    def store(self, key: Dict[str, str], payload: Dict[str, Any]) -> bool:
        """Spill one block's exact K/V arrays. Returns True when the
        entry is durable — an already-present chain key counts (any
        replica may race to spill a popular prefix; double-spill is an
        idempotent no-op), False when an injected drop swallowed the
        blob (no index is written, so readers see a plain miss)."""
        faults.point("kv_store.spill")
        key = dict(key)
        digest_key = self.key_digest(key)
        existing = self._read_index(digest_key)
        if existing is not None and existing.get("key") == key:
            self._note("duplicate_stores")
            return True
        doc = pickle.dumps({"format": 1, "key": key, "payload": payload},
                           protocol=pickle.HIGHEST_PROTOCOL)
        digest = _sha256_bytes(doc)
        data = doc
        keep = faults.truncate_bytes("kv_store.spill")
        if keep is not None:
            # injected torn spill: truncated bytes land under the full
            # digest's key — the fetch-side digest check convicts
            data = doc[:keep]
        if self._blobs.put(data, digest=digest) is None:
            return False
        index = {"format": 1, "key": key, "blob": digest,
                 "size": len(doc), "created": time.time()}
        rel = self._index_rel(digest_key)
        with tempfile.TemporaryDirectory(prefix="dct-kv-up-") as stage:
            staged = os.path.join(stage, rel)
            os.makedirs(os.path.dirname(staged), exist_ok=True)
            with open(staged, "w") as f:
                json.dump(index, f, indent=1)
            self._inner.upload(stage, CHUNK_NAMESPACE, paths=[rel])
        self._note("stores")
        self._note("bytes_stored", len(doc))
        self._maybe_sweep()
        return True

    def load(self, key: Dict[str, str]) -> Optional[Dict[str, Any]]:
        """Exact K/V payload for a chain key, or None — a plain miss.
        Every failure (missing/torn blob, index pointing at a foreign
        blob, unpickling error) lands here as a miss: the caller
        re-prefills, and wrong K/V is never served."""
        faults.point("kv_store.fetch")
        key = dict(key)
        try:
            entry = self._read_index(self.key_digest(key))
            if entry is None or entry.get("key") != key:
                self._note("misses")
                return None
            doc = pickle.loads(self._blobs.get(str(entry["blob"])))
            if doc.get("key") != key:
                # an index pointing at a foreign blob can only serve
                # WRONG K/V for this prefix — refuse, treat as a miss
                raise ValueError("kv blob key mismatch")
            payload = doc["payload"]
        except Exception as e:  # noqa: BLE001 — any failure is a miss
            logger.warning("kv tier fetch failed (treated as a miss): %s", e)
            self._note("misses")
            self._note("errors")
            return None
        self._note("hits")
        self._note("bytes_loaded", int(entry.get("size", 0)))
        return payload

    def contains(self, key: Dict[str, str]) -> bool:
        """Index-only presence probe (no blob fetch, no counters)."""
        key = dict(key)
        entry = self._read_index(self.key_digest(key))
        return entry is not None and entry.get("key") == key

    def _maybe_sweep(self) -> None:
        if self.budget_bytes is None:
            return
        with self._lock:
            self._since_sweep += 1
            if self._since_sweep < self.sweep_every:
                return
            self._since_sweep = 0
        self.sweep()

    def sweep(self) -> Dict[str, Any]:
        """Apply the byte budget now (LRU-by-mtime over ``cas/kv/``)."""
        if self.budget_bytes is None:
            return {"namespace": "kv", "swept": False,
                    "evicted": 0, "evicted_bytes": 0}
        res = sweep_namespace(self._inner, "kv", self.budget_bytes)
        self._note("evictions", int(res.get("evicted", 0)))
        return res

    def stats(self) -> Dict[str, Any]:
        usage = namespace_usage(self._inner, "kv")
        entries = sum(1 for rel in usage
                      if rel.startswith(KV_INDEX_PREFIX + "/"))
        with self._lock:
            session = dict(self.session)
        looked = session["hits"] + session["misses"]
        return {
            "entries": entries,
            "objects": len(usage),
            "bytes": sum(usage.values()),
            "budget_bytes": self.budget_bytes,
            "hit_rate": (round(session["hits"] / looked, 4)
                         if looked else None),
            "session": session,
        }


class CASStorageManager(StorageManager):
    """Content-addressed wrapper around a concrete storage backend.

    Presents the exact StorageManager interface (logical files in/out), so
    CheckpointContext and the commit protocol are unchanged; the chunking
    is invisible above this layer.
    """

    def __init__(self, inner: StorageManager, *,
                 chunk_size: int = DEFAULT_CHUNK_SIZE,
                 cache: Optional[ChunkCache] = None,
                 pool: Optional[transfer.TransferPool] = None,
                 namespace_budgets: Optional[Dict[str, int]] = None) -> None:
        if chunk_size < 1:
            raise ValueError(f"chunk_size must be >= 1, got {chunk_size}")
        if isinstance(inner, CASStorageManager):
            raise ValueError("cas storage cannot nest another cas store")
        self._inner = inner
        self._chunk_size = chunk_size
        self._cache = cache
        self._pool = pool
        self._lock = threading.Lock()
        # dedup set: chunks believed present in the backend. Rebuilt from a
        # fresh listing on every save (never unioned across saves — a chunk
        # another process GC'd must drop out), plus the chunks this process
        # uploaded itself (object-store listings can lag just-written keys).
        self._known_chunks: Set[str] = set()
        self._session_chunks: Set[str] = set()
        # merged chunk manifests memo: (storage_id, manifest-rel tuple) ->
        # {rel: {"size", "chunks": [{"sha256", "size"}, ...]}}
        self._chunkmap_memo: Dict[Tuple[str, Tuple[str, ...]],
                                  Dict[str, Any]] = {}
        self._registry: Optional[Any] = None
        self._tracer: Optional[Any] = None
        self.session_stats: Dict[str, int] = {
            "bytes_uploaded": 0, "bytes_deduped": 0, "bytes_downloaded": 0,
            "chunks_uploaded": 0, "chunks_deduped": 0, "chunks_dropped": 0,
            "cache_hits": 0, "cache_misses": 0,
        }
        # chunk-namespace client of the shared blob transport; the KV
        # spill tier (kv_store()) is the second client
        self._chunks = BlobService(
            inner, CHUNK_PREFIX, cache=cache,
            fault_store="cas.chunk_upload", fault_drop="cas.chunk_drop",
            fault_load="cas.chunk_download", counter=self._count)
        self._kv_store: Optional[KVBlobStore] = None
        # per-namespace byte budgets ("kv") enforced by
        # sweep_namespaces(); chunk GC keys on checkpoint references,
        # not bytes, so "chunks" is not budgetable here
        self._ns_budgets: Dict[str, int] = dict(namespace_budgets or {})
        bad = set(self._ns_budgets) - {"kv"}
        if bad:
            raise ValueError(
                f"unknown namespace budget(s): {sorted(bad)} "
                "(budgetable namespaces: kv)")
        self._ns_evictions: Dict[str, int] = {"kv": 0}

    # -- telemetry ----------------------------------------------------------

    def set_telemetry(self, registry: Optional[Any],
                      tracer: Optional[Any] = None) -> None:
        self._registry = registry
        self._tracer = tracer

    def _span(self, name: str):
        if self._tracer is not None:
            return self._tracer.span(name)
        return contextlib.nullcontext()

    def _count(self, key: str, n: int) -> None:
        with self._lock:
            self.session_stats[key] += n
        if self._registry is not None:
            self._registry.counter(
                f"cas_{key}_total",
                "content-addressed checkpoint store transfer accounting",
            ).inc(n)

    # -- helpers ------------------------------------------------------------

    def _get_pool(self) -> transfer.TransferPool:
        return self._pool if self._pool is not None else transfer.get_pool()

    def _scan_chunks(self, path: str) -> List[Dict[str, Any]]:
        """[{sha256, size, offset}] for one file, in order."""
        out: List[Dict[str, Any]] = []
        offset = 0
        with open(path, "rb") as f:
            for data in iter(lambda: f.read(self._chunk_size), b""):
                out.append({"sha256": _sha256_bytes(data),
                            "size": len(data), "offset": offset})
                offset += len(data)
        if not out:  # empty file: zero chunks, size 0 — still restorable
            return []
        return out

    def _list_backend_chunks(self) -> Set[str]:
        """Digests present in the chunk namespace RIGHT NOW (fresh listing,
        no session memo) — what dedup re-verification checks against.
        Spilled KV blobs (``kv/...``) are a different namespace and
        never appear here."""
        return set(self._chunks.list_blobs())

    def _refresh_known_chunks(self) -> Set[str]:
        digests = self._list_backend_chunks()
        with self._lock:
            # REBUILT, not unioned: unioning forever would keep chunks that
            # another process's GC reclaimed 'known' for the lifetime of a
            # long-running trainer, deduping every later save against bytes
            # the backend no longer has
            self._known_chunks = digests | self._session_chunks
            return set(self._known_chunks)

    def _chunkmaps(self, storage_id: str,
                   manifest_rels: Iterable[str]) -> Dict[str, Any]:
        key = (storage_id, tuple(sorted(manifest_rels)))
        with self._lock:
            if key in self._chunkmap_memo:
                return self._chunkmap_memo[key]
        merged: Dict[str, Any] = {}
        with tempfile.TemporaryDirectory(prefix="dct-cas-") as tmp:
            self._inner.download(storage_id, tmp, paths=list(key[1]))
            for rel in key[1]:
                try:
                    with open(os.path.join(tmp, rel)) as f:
                        doc = json.load(f)
                except (ValueError, OSError) as e:
                    raise _corrupt(
                        storage_id, f"unreadable chunk manifest {rel!r}: {e}"
                    ) from None
                merged.update(doc.get("files") or {})
        with self._lock:
            self._chunkmap_memo[key] = merged
        return merged

    def _forget(self, storage_id: str) -> None:
        with self._lock:
            for key in [k for k in self._chunkmap_memo
                        if k[0] == storage_id]:
                del self._chunkmap_memo[key]

    # -- upload -------------------------------------------------------------

    def upload(self, src_dir: str, storage_id: str,
               paths: Optional[List[str]] = None) -> None:
        rels = paths if paths is not None else _walk_relative(src_dir)
        passthrough = [r for r in rels if _is_passthrough(r)]
        chunked = [r for r in rels if not _is_passthrough(r)]
        with self._span("cas_upload"):
            # protocol files go first and verbatim, so a partial upload is
            # still self-identifying to validate_checkpoint_dir
            if passthrough:
                self._inner.upload(src_dir, storage_id, paths=passthrough)
            if not chunked:
                return
            known = self._refresh_known_chunks()
            entries: Dict[str, Any] = {}
            to_send: List[Tuple[str, str, Dict[str, Any]]] = []
            seen_this_call: Set[str] = set()
            # digest -> (src path, chunk) for chunks skipped as already
            # present, kept so _verify_dedup can re-upload any that a
            # concurrent GC reclaimed during this window
            dedup_src: Dict[str, Tuple[str, Dict[str, Any]]] = {}
            for rel in chunked:
                src = os.path.join(src_dir, rel)
                chunks = self._scan_chunks(src)
                entries[rel] = {
                    "size": sum(c["size"] for c in chunks),
                    "chunks": [{"sha256": c["sha256"], "size": c["size"]}
                               for c in chunks],
                }
                for c in chunks:
                    d = c["sha256"]
                    if d in seen_this_call:
                        self._count("bytes_deduped", c["size"])
                        self._count("chunks_deduped", 1)
                        continue
                    if d in known:
                        self._count("bytes_deduped", c["size"])
                        self._count("chunks_deduped", 1)
                        dedup_src.setdefault(d, (src, c))
                        continue
                    seen_this_call.add(d)
                    to_send.append((src, rel, c))
            # the chunk manifest goes BEFORE the chunk data: once it is
            # durable, a concurrent GC's ref-count walk sees every chunk
            # this save references — including the deduped ones it will
            # never upload — and keeps them (delete() walks twice for the
            # manifests that land mid-walk)
            self._write_chunk_manifest(storage_id, entries)
            if to_send:
                self._upload_chunks(to_send)
                uploaded = {c["sha256"] for _, _, c in to_send}
                with self._lock:
                    self._known_chunks |= uploaded
                    self._session_chunks |= uploaded
            self._verify_dedup(dedup_src)

    def _verify_dedup(
            self,
            dedup_src: Dict[str, Tuple[str, Dict[str, Any]]]) -> None:
        """Dedup decisions are provisional until confirmed AFTER the chunk
        manifest is durable: a GC whose ref-count walk predates the
        manifest cannot see this save's references, so it may have
        reclaimed a chunk the save skipped as already present. Re-check
        every deduped digest against a fresh backend listing and re-upload
        the ones that vanished — the manifest is visible now, so later GC
        walks keep them."""
        if not dedup_src:
            return
        present = self._list_backend_chunks()
        missing = set(dedup_src) - present
        if not missing:
            return
        logger.warning(
            "cas: %d deduped chunk(s) vanished from the backend during the "
            "save (concurrent GC); re-uploading", len(missing))
        self._upload_chunks([(src, "", c)
                             for d, (src, c) in sorted(dedup_src.items())
                             if d in missing])
        with self._lock:
            self._known_chunks |= missing
            self._session_chunks |= missing

    def _upload_chunks(
            self, to_send: List[Tuple[str, str, Dict[str, Any]]]) -> None:
        def send(src: str, chunk: Dict[str, Any]) -> None:
            digest, size, offset = (chunk["sha256"], chunk["size"],
                                    chunk["offset"])
            with open(src, "rb") as f:
                f.seek(offset)
                data = f.read(size)
            if self._chunks.put(data, digest=digest) is None:
                # injected lost object (cas.chunk_drop): the save
                # "succeeds" but this chunk never reaches the backend —
                # restore must refuse
                self._count("chunks_dropped", 1)
                return
            self._count("bytes_uploaded", size)
            self._count("chunks_uploaded", 1)

        tasks = [
            (lambda src=src, chunk=c: send(src, chunk))
            for src, _, c in to_send
        ]
        self._get_pool().run(tasks)

    def _write_chunk_manifest(self, storage_id: str,
                              entries: Dict[str, Any]) -> None:
        token = uuid.uuid4().hex[:10]
        rel = f"{CHUNK_MANIFEST_PREFIX}{token}.json"
        with tempfile.TemporaryDirectory(prefix="dct-cas-mf-") as tmp:
            with open(os.path.join(tmp, rel), "w") as f:
                json.dump({
                    "format": 1,
                    "storage_id": storage_id,
                    "chunk_size": self._chunk_size,
                    "files": entries,
                }, f, indent=1)
            self._inner.upload(tmp, storage_id, paths=[rel])
        self._forget(storage_id)

    # -- download -----------------------------------------------------------

    def download(self, storage_id: str, dst_dir: str,
                 paths: Optional[List[str]] = None) -> None:
        listing = self._inner.list_files(storage_id)
        manifest_rels = sorted(r for r in listing if _is_chunk_manifest(r))
        if not manifest_rels:
            # not CAS-written (plain checkpoint in the same root): verbatim
            self._inner.download(storage_id, dst_dir, paths=paths)
            return
        with self._span("cas_download"):
            chunkmap = self._chunkmaps(storage_id, manifest_rels)
            if paths is not None:
                want = list(paths)
            else:
                want = sorted((set(listing) - set(manifest_rels))
                              | set(chunkmap))
            plain = [r for r in want if r not in chunkmap]
            assemble = [r for r in want if r in chunkmap]
            if plain:
                self._inner.download(storage_id, dst_dir, paths=plain)
            tasks = [
                (lambda rel=rel: self._assemble_file(
                    storage_id, rel, chunkmap[rel],
                    os.path.join(dst_dir, rel)))
                for rel in assemble
            ]
            self._get_pool().run(tasks)

    def _assemble_file(self, storage_id: str, rel: str,
                       entry: Dict[str, Any], out: str) -> None:
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        with open(out, "wb") as f:
            for chunk in entry.get("chunks") or []:
                f.write(self._fetch_chunk(storage_id, chunk["sha256"],
                                          chunk["size"]))
        size = os.path.getsize(out)
        if size != entry.get("size", size):
            raise _corrupt(
                storage_id, f"file {rel!r} assembled to {size} bytes, chunk "
                f"manifest says {entry['size']}")

    def _fetch_chunk(self, storage_id: str, digest: str, size: int) -> bytes:
        try:
            return self._chunks.get(digest)
        except BlobIntegrityError as e:
            if e.missing:
                raise _corrupt(
                    storage_id, f"chunk {digest[:12]}… missing from the "
                    "chunk store (lost object or over-eager GC)") from None
            raise _corrupt(
                storage_id, f"chunk {digest[:12]}… content digest mismatch "
                "(torn chunk)") from None

    # -- logical listing / commit -------------------------------------------

    def list_files(self, storage_id: str) -> Dict[str, int]:
        listing = self._inner.list_files(storage_id)
        manifest_rels = sorted(r for r in listing if _is_chunk_manifest(r))
        out = {r: s for r, s in listing.items()
               if not _is_chunk_manifest(r)}
        if manifest_rels:
            chunkmap = self._chunkmaps(storage_id, manifest_rels)
            for rel, entry in chunkmap.items():
                out[rel] = int(entry.get("size", 0))
        return out

    def commit(self, storage_id: str,
               payload: Optional[Dict[str, Any]] = None) -> None:
        self._inner.commit(storage_id, payload)

    def is_committed(self, storage_id: str) -> bool:
        return self._inner.is_committed(storage_id)

    def list_storage_ids(self) -> List[str]:
        return [sid for sid in self._inner.list_storage_ids()
                if sid != CHUNK_NAMESPACE]

    def storage_age_s(self, storage_id: str) -> Optional[float]:
        return self._inner.storage_age_s(storage_id)

    # -- delete + chunk ref-counting GC --------------------------------------

    def _referenced_digests(self, storage_id: str) -> Set[str]:
        listing = self._inner.list_files(storage_id)
        manifest_rels = sorted(r for r in listing if _is_chunk_manifest(r))
        if not manifest_rels:
            return set()
        chunkmap = self._chunkmaps(storage_id, manifest_rels)
        return {c["sha256"] for entry in chunkmap.values()
                for c in entry.get("chunks") or []}

    def _survivor_references(self, deleted_id: str) -> Optional[Set[str]]:
        """Union of chunk digests referenced by every surviving checkpoint
        dir, or None when the ref-count is unknowable (the backend cannot
        enumerate, or a neighbor's manifests are unreadable) — the caller
        must then keep every chunk."""
        try:
            survivors = self.list_storage_ids()
        except NotImplementedError:
            logger.info("chunk GC skipped: %s cannot enumerate checkpoints",
                        type(self._inner).__name__)
            return None
        out: Set[str] = set()
        for sid in survivors:
            if sid == deleted_id:
                continue
            try:
                out |= self._referenced_digests(sid)
            except Exception as e:
                # an unreadable neighbor makes the ref-count unknowable:
                # keep every chunk rather than risk deleting a live one
                logger.warning(
                    "chunk GC aborted: cannot read chunk manifests of %s "
                    "(%s); keeping all chunks", sid, e)
                return None
        return out

    def delete(self, storage_id: str) -> None:
        """Delete a checkpoint, then reclaim chunks nothing references.

        Ref-counting is recomputed from the surviving checkpoint dirs —
        committed AND uncommitted. In-flight saves are protected by three
        interlocking rules rather than any storage-level lock:

        1. upload() writes the chunk manifest BEFORE any chunk data, so a
           save's references (including chunks it deduped and will never
           upload) become visible to this walk as early as possible;
        2. the ref-count walk here runs TWICE, and a chunk is reclaimed
           only when BOTH walks found it unreferenced — a manifest that
           lands while the first walk is reading its neighbors still
           protects its chunks (manifests are immutable and memoized, so
           the second walk only re-lists and reads manifests that are
           actually new);
        3. a save whose dedup nevertheless raced a GC that completed
           before its manifest landed re-verifies its deduped chunks
           against a fresh listing and re-uploads any that vanished
           (upload()/_verify_dedup) before the save returns.
        """
        try:
            doomed = self._referenced_digests(storage_id)
        except Exception as e:  # unreadable manifests: skip chunk GC (safe)
            logger.warning("chunk GC skipped for %s: %s", storage_id, e)
            doomed = set()
        self._inner.delete(storage_id)
        self._forget(storage_id)
        if not doomed:
            return
        referenced: Set[str] = set()
        garbage = set(doomed)
        for _ in range(2):
            if not garbage:
                return
            refs = self._survivor_references(storage_id)
            if refs is None:
                return
            referenced |= refs
            garbage = doomed - referenced
        if not garbage:
            return
        try:
            # only ever the chunk namespace: spilled KV entries
            # (cas/kv/...) are referenced via their own index, live in a
            # different BlobService prefix, and are structurally invisible
            # to this ref-count walk — never swept as orphan chunks
            self._chunks.delete(garbage)
        except NotImplementedError:
            logger.info("chunk GC skipped: %s has no per-object delete",
                        type(self._inner).__name__)
            return
        with self._lock:
            self._known_chunks -= garbage
            self._session_chunks -= garbage
        logger.info("chunk GC: removed %d chunks unreferenced after "
                    "deleting %s (%d still referenced)",
                    len(garbage), storage_id, len(referenced & doomed))

    # -- stats (dct checkpoint stats) ----------------------------------------

    def kv_store(self) -> KVBlobStore:
        """The KV spill tier sharing this manager's backend: spilled
        K/V blocks land in ``cas/kv/`` next to (but namespaced away
        from) the checkpoint chunks. Built lazily — a deployment that
        never serves pays nothing. Inherits this manager's ``kv``
        namespace budget, if one was configured."""
        with self._lock:
            if self._kv_store is None:
                self._kv_store = KVBlobStore(
                    self._inner, budget_bytes=self._ns_budgets.get("kv"))
            return self._kv_store

    def sweep_namespaces(self) -> Dict[str, Any]:
        """Enforce every configured namespace byte budget now
        (LRU-by-mtime; see :func:`sweep_namespace`). Returns the
        per-namespace sweep reports; eviction totals accumulate into
        ``storage_stats()['namespaces'][ns]['evictions']``."""
        out: Dict[str, Any] = {}
        for ns in sorted(self._ns_budgets):
            res = sweep_namespace(self._inner, ns, self._ns_budgets[ns])
            with self._lock:
                self._ns_evictions[ns] = (self._ns_evictions.get(ns, 0)
                                          + int(res.get("evicted", 0)))
            out[ns] = res
        return out

    def storage_stats(self) -> Dict[str, Any]:
        """Durable store-wide dedup accounting + cache hit rate, broken
        out per blob namespace (checkpoint chunks vs spilled KV blocks —
        one aggregate would let a growing KV tier masquerade as
        checkpoint growth).

        dedup_ratio = logical chunked bytes across every checkpoint's
        manifests / physical bytes in the chunk namespace — >1 means
        chunk-level dedup is saving space (and saved the matching upload
        bandwidth when the chunks were first written).
        """
        listing = self._inner.list_files(CHUNK_NAMESPACE)
        physical = {rel: size for rel, size in listing.items()
                    if self._chunks.digest_of_rel(rel) is not None}
        kv_bytes = sum(size for rel, size in listing.items()
                       if rel.startswith("kv/"))
        kv_objects = sum(1 for rel in listing if rel.startswith("kv/"))
        kv_entries = sum(
            1 for rel in listing if rel.startswith(KV_INDEX_PREFIX + "/"))
        chunk_bytes = sum(physical.values())
        logical = 0
        checkpoints = 0
        try:
            sids = self.list_storage_ids()
        except NotImplementedError:
            sids = []
        for sid in sids:
            try:
                listing = self._inner.list_files(sid)
                manifest_rels = sorted(r for r in listing
                                       if _is_chunk_manifest(r))
                if not manifest_rels:
                    continue
                chunkmap = self._chunkmaps(sid, manifest_rels)
            except Exception as e:
                logger.warning("stats: skipping unreadable checkpoint %s "
                               "(%s)", sid, e)
                continue
            checkpoints += 1
            logical += sum(int(entry.get("size", 0))
                           for entry in chunkmap.values())
        out: Dict[str, Any] = {
            "chunk_count": len(physical),
            "chunk_bytes": chunk_bytes,
            "cas_checkpoints": checkpoints,
            "logical_bytes": logical,
            "dedup_ratio": (round(logical / chunk_bytes, 4)
                            if chunk_bytes else None),
            "namespaces": {
                "chunks": {"objects": len(physical),
                           "bytes": chunk_bytes},
                "kv": {"objects": kv_objects,
                       "bytes": kv_bytes,
                       "entries": kv_entries,
                       "budget_bytes": self._ns_budgets.get("kv"),
                       "evictions": self._ns_evictions.get("kv", 0)},
            },
            "session": dict(self.session_stats),
        }
        if self._cache is not None:
            out["cache"] = self._cache.stats()
        return out


def build_cas(cfg: Any, inner: StorageManager) -> CASStorageManager:
    """Construct from a ``checkpoint_storage: {type: cas, ...}`` config
    block (config/experiment.py) and an already-built inner backend."""
    cache = None
    if cfg.cache_path:
        cache = ChunkCache(
            cfg.cache_path,
            max_bytes=int(cfg.cache_size_mb or 256) << 20)
    pool = None
    if cfg.transfer_workers is not None:
        pool = transfer.TransferPool(workers=int(cfg.transfer_workers))
    return CASStorageManager(
        inner,
        chunk_size=int(cfg.chunk_size_kb or 1024) << 10,
        cache=cache,
        pool=pool,
    )
