"""Checkpoint GC task — deletes doomed checkpoints from storage.

≈ the reference's GC container (master/internal/checkpoint_gc.go:27 spawns
it; harness/determined/exec/gc_checkpoints.py:97 does the deleting). The
master marks records deleted in its registry, then schedules this zero-slot
command task with the storage config + uuid list in env.
"""
from __future__ import annotations

import json
import os
import sys


def sweep_uncommitted(manager) -> int:
    """Delete orphaned uncommitted checkpoint dirs (crash leftovers).

    A save that died between upload and COMMIT leaves a directory no
    restore will ever accept (core/_checkpoint.py refuses it), so once it
    is old enough to rule out an in-flight save it is garbage. Opt-in via
    DCT_GC_SWEEP_UNCOMMITTED=1; the age floor (DCT_GC_UNCOMMITTED_AGE_S,
    default 3600s) is what keeps a concurrent save's half-written dir
    safe from us.
    """
    age_floor = float(os.environ.get("DCT_GC_UNCOMMITTED_AGE_S", "3600"))
    try:
        storage_ids = manager.list_storage_ids()
    except NotImplementedError:
        print("storage backend cannot enumerate checkpoints; "
              "skipping uncommitted sweep")
        return 0
    swept = failed = 0
    for sid in storage_ids:
        if sid == "cas":
            # the content-addressed namespace (storage/cas.py) is not a
            # checkpoint and never has a COMMIT marker: it holds the chunk
            # store AND the spilled KV blocks (cas/kv/ blobs + index),
            # neither of which may ever be swept as "uncommitted". A CAS
            # manager already hides it, but guard here too for legacy GC
            # configs pointing directly at the inner store
            continue
        try:
            if manager.is_committed(sid):
                continue
            age = manager.storage_age_s(sid)
            if age is None or age < age_floor:
                continue
            manager.delete(sid)
            print(f"swept uncommitted checkpoint {sid} (age {age:.0f}s)")
            swept += 1
        except Exception as exc:  # keep going; report at the end
            print(f"failed to sweep {sid}: {exc}")
            failed += 1
    print(f"uncommitted sweep: {swept} deleted, {failed} failed")
    return failed


def main() -> int:
    from determined_clone_tpu.config.experiment import CheckpointStorageConfig
    from determined_clone_tpu.storage import build

    storage_raw = os.environ.get("DCT_GC_STORAGE")
    uuids_raw = os.environ.get("DCT_GC_UUIDS", "")
    if not storage_raw:
        print("DCT_GC_STORAGE not set; nothing to do")
        return 0
    # when DCT_GC_STORAGE is a `type: cas` block, delete() below also runs
    # the ref-counted chunk GC: chunks still referenced by any surviving
    # checkpoint are kept, and the kv/ namespace is outside the chunk
    # walk entirely — spilled KV blocks are never reclaimed here
    # (storage/cas.py, docs/checkpoint_storage.md)
    manager = build(CheckpointStorageConfig.from_dict(json.loads(storage_raw)))
    uuids = [u for u in uuids_raw.split(",") if u]
    failed = 0
    for uuid in uuids:
        try:
            manager.delete(uuid)
            print(f"deleted checkpoint {uuid}")
        except FileNotFoundError:
            print(f"checkpoint {uuid} already gone")
        except Exception as exc:  # keep going; report at the end
            print(f"failed to delete {uuid}: {exc}")
            failed += 1
    print(f"gc done: {len(uuids) - failed}/{len(uuids)} deleted")
    if os.environ.get("DCT_GC_SWEEP_UNCOMMITTED") == "1":
        failed += sweep_uncommitted(manager)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
