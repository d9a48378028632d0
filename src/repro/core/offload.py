"""Memory-tier stores for offloading: Disk (np.memmap files), Host (RAM
arrays), Device (jax arrays).

On this container the "device" is the CPU jax backend, but the tier
*structure* and data movement are real: DiskStore does real file I/O,
HostStore holds pinned numpy buffers, DeviceStore jax Arrays.  On TPU the
same interfaces map to (remote store / host DRAM / HBM).  Every store
tracks bytes for the Table-6 memory-footprint benchmark.
"""
from __future__ import annotations

import os
import threading
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Optional

import jax
import numpy as np


class Store:
    name = "base"

    def __init__(self):
        self._items: Dict[str, object] = {}
        self._bytes = 0
        self._peak = 0
        self._lock = threading.Lock()

    def _account(self, delta: int):
        with self._lock:
            self._bytes += delta
            self._peak = max(self._peak, self._bytes)

    @property
    def bytes_used(self) -> int:
        return self._bytes

    @property
    def peak_bytes(self) -> int:
        return self._peak

    def keys(self):
        return list(self._items)

    def __contains__(self, key):
        return key in self._items

    def delete(self, key: str):
        item = self._items.pop(key, None)
        if item is not None:
            self._account(-self._nbytes(item))

    @staticmethod
    def _nbytes(x) -> int:
        return int(getattr(x, "nbytes", 0))


class HostStore(Store):
    """CPU-memory tier: numpy arrays."""

    name = "host"

    def put(self, key: str, arr: np.ndarray):
        arr = np.asarray(arr)
        if key in self._items:
            self.delete(key)
        self._items[key] = arr
        self._account(arr.nbytes)
        return arr

    def get(self, key: str) -> np.ndarray:
        return self._items[key]


class DeviceStore(Store):
    """Device (HBM analogue) tier: jax Arrays."""

    name = "device"

    def put(self, key: str, arr):
        arr = jax.device_put(arr)
        if key in self._items:
            self.delete(key)
        arr.block_until_ready()
        self._items[key] = arr
        self._account(arr.nbytes)
        return arr

    def get(self, key: str):
        return self._items[key]


class DiskStore(Store):
    """NVMe tier: one file per tensor under ``root``; reads go through
    np.fromfile on a preopened path (real disk I/O on this container)."""

    name = "disk"

    def __init__(self, root: str):
        super().__init__()
        self.root = Path(root)
        self.root.mkdir(parents=True, exist_ok=True)
        self._meta: Dict[str, tuple] = {}

    def _path(self, key: str) -> Path:
        return self.root / (key.replace("/", "_") + ".bin")

    def put(self, key: str, arr: np.ndarray):
        arr = np.ascontiguousarray(arr)
        path = self._path(key)
        arr.tofile(path)
        self._meta[key] = (arr.shape, arr.dtype)
        self._items[key] = path
        self._account(arr.nbytes)
        return path

    def meta(self, key: str):
        return self._meta[key]

    def get(self, key: str) -> np.ndarray:
        shape, dtype = self._meta[key]
        out = np.fromfile(self._path(key), dtype=dtype)
        return out.reshape(shape)

    def read_range(self, key: str, offset_bytes: int, size_bytes: int,
                   out: np.ndarray):
        """Read a byte range into a preallocated buffer (blockwise path)."""
        with open(self._path(key), "rb", buffering=0) as f:
            f.seek(offset_bytes)
            data = f.read(size_bytes)
        flat = out.reshape(-1).view(np.uint8)
        flat[offset_bytes:offset_bytes + len(data)] = np.frombuffer(
            data, np.uint8)
        return len(data)

    def drop_cache(self, key: str):
        """Evict the file from the OS page cache (POSIX_FADV_DONTNEED) so
        benchmarks measure real disk reads, not memcpy — the paper's NVMe
        regime."""
        try:
            with open(self._path(key), "rb") as f:
                os.fsync(f.fileno())
                os.posix_fadvise(f.fileno(), 0, 0, os.POSIX_FADV_DONTNEED)
            return True
        except (OSError, AttributeError):
            return False


@dataclass
class MemoryBudget:
    """Tier capacities for autoconfig (bytes).  The defaults describe the
    paper's laptop; ``for_device`` replaces the device capacity with what
    the backend reports."""
    device: int = 6 * 2**30        # paper laptop: RTX3060 6GB
    host: int = 16 * 2**30         # 16GB DRAM
    disk: int = 1 * 2**40          # 1TB SSD
    device_bw: float = 12e9        # PCIe x8-ish GPU link (B/s)
    disk_bw: float = 3.5e9         # NVMe read bw (B/s)

    @classmethod
    def for_device(cls, device=None) -> "MemoryBudget":
        """The default budget with ``device`` capacity taken from the
        backend's ``memory_stats()["bytes_limit"]`` (first local device
        unless one is given).  A backend that reports no capacity (the
        CPU reports none) keeps the default."""
        dev = device if device is not None else jax.devices()[0]
        limit = (dev.memory_stats() or {}).get("bytes_limit")
        return cls(device=int(limit)) if limit else cls()
