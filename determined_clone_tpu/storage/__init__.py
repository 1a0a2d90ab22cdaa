"""Checkpoint storage backends (≈ harness/determined/common/storage)."""
from determined_clone_tpu.storage.base import (
    AzureStorageManager,
    DirectoryStorageManager,
    GCSStorageManager,
    S3StorageManager,
    SharedFSStorageManager,
    StorageManager,
    build,
)
from determined_clone_tpu.storage.cas import (
    BlobIntegrityError,
    BlobService,
    CASStorageManager,
    ChunkCache,
)
from determined_clone_tpu.storage.transfer import (
    TransferPool,
    get_pool,
    reset_pool,
)

__all__ = [
    "AzureStorageManager",
    "BlobIntegrityError",
    "BlobService",
    "CASStorageManager",
    "ChunkCache",
    "DirectoryStorageManager",
    "GCSStorageManager",
    "S3StorageManager",
    "SharedFSStorageManager",
    "StorageManager",
    "TransferPool",
    "build",
    "get_pool",
    "reset_pool",
]
