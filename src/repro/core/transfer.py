"""PIPO data-transfer suite (paper §3.3 + Appendix A).

Three techniques, replacing single-call I/O:
  * blockwise transfer   — tensors move in fixed-size blocks so the
    disk->host and host->device stages overlap (Fig. 3);
  * multi-thread parallel transfer — multiple reader threads each own a
    chunk of the block stream, keeping the NVMe queue full;
  * data merging         — all weight tensors of a layer are stored as ONE
    contiguous buffer + manifest, so a layer is one I/O request.

Block size is picked empirically per device by ``sweep_block_size``
(Appendix A reproduces Fig. 6 with it).
"""
from __future__ import annotations

import math
import queue
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.offload import DiskStore
from repro.core.tasks import phase

DEFAULT_BLOCK = 8 * 2**20          # 8MB disk blocks (paper Appendix A)
DEVICE_BLOCK = 32 * 2**20          # 32MB host->device blocks


# ---------------------------------------------------------------------------
# Data merging
# ---------------------------------------------------------------------------

@dataclass
class Manifest:
    """Layout of tensors merged into one flat uint8 buffer."""
    entries: Dict[str, tuple]       # name -> (offset, shape, dtype)
    total_bytes: int


def merge_tensors(tensors: Dict[str, np.ndarray]) -> tuple[np.ndarray, Manifest]:
    """Flatten a unit's tensors (sorted by name) into one contiguous
    uint8 buffer + manifest, so one layer is ONE I/O request (§3.3)."""
    entries, off = {}, 0
    for name, a in sorted(tensors.items()):
        a = np.ascontiguousarray(a)
        entries[name] = (off, a.shape, a.dtype)
        off += a.nbytes
    buf = np.empty(off, np.uint8)
    for name, a in sorted(tensors.items()):
        o, shape, dtype = entries[name]
        buf[o:o + a.nbytes] = np.ascontiguousarray(a).view(np.uint8).reshape(-1)
    return buf, Manifest(entries, off)


def split_views(buf: np.ndarray, manifest: Manifest) -> Dict[str, np.ndarray]:
    """Zero-copy views back out of a merged buffer (inverse of
    merge_tensors)."""
    out = {}
    for name, (off, shape, dtype) in manifest.entries.items():
        n = int(np.prod(shape)) * np.dtype(dtype).itemsize
        out[name] = buf[off:off + n].view(dtype).reshape(shape)
    return out


# ---------------------------------------------------------------------------
# INT4 streaming (paper §3.4: W4 weights quarter the transfer bytes)
# ---------------------------------------------------------------------------

QUANT_MIN_GROUP = 16


def int4_group(arr) -> Optional[int]:
    """The groupwise-quantization group size for one tensor, or None if
    the tensor streams unquantized.  Eligible: 2-D, an even number of
    columns, and a contraction dim divisible by a reasonable group (the
    gcd with the canonical 128 — full-size layers get 128, scaled-down
    test configs a smaller power of two).  This predicate is THE single
    source of truth shared by the engines' streaming path and the
    resident INT4 reference used in parity tests."""
    from repro.quant.int4 import GROUP
    shape = getattr(arr, "shape", ())
    if len(shape) != 2 or shape[1] % 2 != 0:
        return None
    g = math.gcd(int(shape[0]), GROUP)
    return g if g >= QUANT_MIN_GROUP else None


def quantize_unit(tensors: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """Quantize a unit's eligible tensors to packed INT4: each eligible
    ``name`` is replaced by ``name#q`` (packed uint8, half the columns)
    and ``name#s`` (groupwise f32 scales); ineligible tensors (norm
    vectors, small/odd projections) pass through.  Runs once at engine
    build time (main thread)."""
    from repro.quant.int4 import quantize_int4
    out = {}
    for name, arr in tensors.items():
        g = int4_group(arr)
        if g is None:
            out[name] = np.asarray(arr)
            continue
        packed, scale = quantize_int4(jnp.asarray(arr, jnp.float32), g)
        out[name + "#q"] = np.asarray(packed)
        out[name + "#s"] = np.asarray(scale)
    return out


def int4_roundtrip(arr):
    """quantize -> dequantize one tensor through the exact jitted dequant
    the streaming path uses — builds the resident INT4 reference whose
    decode tokens the INT4 offloaded engine must match bit-for-bit.
    Ineligible tensors return unchanged."""
    from repro.quant.int4 import quantize_int4
    g = int4_group(arr)
    if g is None:
        return arr
    packed, scale = quantize_int4(jnp.asarray(arr, jnp.float32), g)
    # packed/scale are already device arrays — feed them straight to the
    # jitted dequant, no host bounce
    return np.asarray(_fused_dequant(packed, scale, g))


# ---------------------------------------------------------------------------
# Transfers
# ---------------------------------------------------------------------------


@dataclass
class SimLink:
    """Fixed-bandwidth interconnect model shared by EVERY transfer that
    crosses the offload boundary — weight loads (``TieredWeightStore``)
    and KV loads (``core.kvstore.TieredKVStore``) hold the same instance,
    so both pay the same link.  ``floor(nbytes, t0)`` sleeps out the
    remainder of ``nbytes / bw`` seconds since ``t0`` (GIL released, like
    a DMA engine); ``bw=None`` disables the floor."""

    bw: Optional[float] = None

    def floor(self, nbytes: int, t0: float):
        if self.bw:
            remain = nbytes / self.bw - (time.perf_counter() - t0)
            if remain > 0:
                time.sleep(remain)


class TieredWeightStore:
    """Merged-buffer weight tiering shared by the generation engine
    (core.engine.PipelinedLM) and the offloaded serving engine
    (serving.offload_engine.OffloadedServingEngine).

    ``put`` merges a unit's tensors into ONE contiguous buffer + manifest on
    the placement tier (device/host/disk); ``load`` moves it to the device
    and splits views, transparently dequantizing INT4 pairs (fused inside
    jit when ``fused_int4``, else materialized — the Fig. 9 ablation knob).

    ``sim_bw`` (bytes/s) floors each load's wall time at
    ``total_bytes / sim_bw``, emulating a fixed-bandwidth interconnect
    (PCIe/NVMe per ``offload.MemoryBudget``).  On this CPU-only container
    host->"device" copies are memcpys whose speed varies with CPU
    contention and page-cache state; the floor makes pipeline-overlap
    benchmarks deterministic, and it sleeps (GIL released) so transfer
    threads overlap compute exactly like a DMA engine would.
    """

    def __init__(self, *, placement: str, host, device, disk,
                 quant: Optional[str] = None, fused_int4: bool = True,
                 block_bytes: int = DEFAULT_BLOCK, n_io_threads: int = 3,
                 cold_reads: bool = False, sim_bw: Optional[float] = None,
                 target=None):
        assert placement in ("device", "host", "disk"), placement
        self.placement = placement
        # the jax device loads land on (None: the default device); a
        # pipeline stage's store targets that stage's chip
        self.target = target
        self.host, self.device, self.disk = host, device, disk
        self.quant = quant
        self.fused_int4 = fused_int4
        self.block_bytes = block_bytes
        self.n_io_threads = n_io_threads
        self.cold_reads = cold_reads
        self.link = SimLink(sim_bw)
        self.manifests: Dict[str, Manifest] = {}
        # per-key load counters (thread-safe enough for CPython dict ops):
        # benchmarks/tests read these to assert transfer volumes, e.g. the
        # MoE routed-union invariant (union bytes < whole-bank bytes).
        self.load_counts: Dict[str, int] = {}

    def put(self, key: str, tensors: Dict[str, np.ndarray]):
        """Merge + place a unit's tensors on the placement tier (main
        thread, done once at engine build)."""
        buf, man = merge_tensors(tensors)
        self.manifests[key] = man
        if self.placement == "disk":
            self.disk.put(key, buf)
        elif self.placement == "host":
            self.host.put(key, buf)
        else:
            self.device.put(key, buf)

    def nbytes(self, key: str) -> int:
        """Bytes one load() of ``key`` moves over the link (packed bytes
        for INT4 units).  Any thread; non-blocking."""
        return self.manifests[key].total_bytes

    @property
    def sim_bw(self) -> Optional[float]:
        return self.link.bw

    def sim_floor(self, nbytes: int, t0: float):
        """Sleep out the remainder of ``nbytes / sim_bw`` seconds since t0
        (delegates to the shared ``SimLink``)."""
        self.link.floor(nbytes, t0)

    def load(self, key: str) -> Dict[str, np.ndarray]:
        """Placement tier -> device tensors (one I/O request per unit).
        Blocking; runs on whatever thread calls it — in the pipeline that
        is a transfer-pool worker, never the compute (main) thread."""
        t0 = time.perf_counter()
        man = self.manifests[key]
        self.load_counts[key] = self.load_counts.get(key, 0) + 1
        with phase("stage"):
            if self.placement == "device":
                buf = self.device.get(key)
                views = split_views(np.asarray(buf), man)
            elif self.placement == "host":
                views = split_views(self.host.get(key), man)
            else:
                if self.cold_reads:
                    # evict page cache: measure real NVMe reads (paper
                    # regime)
                    self.disk.drop_cache(key)
                host_buf = blockwise_disk_to_host(
                    self.disk, key, block_bytes=self.block_bytes,
                    n_threads=self.n_io_threads)
                views = split_views(host_buf.view(np.uint8), man)
        # the link's phases: ``put`` carries the bytes, ``ready`` (and the
        # simulated link's floor) moves the same ones
        with phase("put", man.total_bytes):
            dev = {}
            for name, arr in views.items():
                dev[name] = jax.device_put(arr, self.target)
        with phase("ready"):
            for a in dev.values():
                a.block_until_ready()
            self.sim_floor(man.total_bytes, t0)
        if self.quant != "int4":
            return dev
        with phase("dequant"):
            return self._maybe_dequant(dev)

    def _maybe_dequant(self, dev):
        """Dequantize INT4 ``#q``/``#s`` pairs after the (cheap, packed)
        bytes crossed the link.  Called from ``load`` on a transfer-pool
        thread: the fused path dispatches one jitted dequant whose cost
        overlaps the main thread's compute on earlier layers — only INT4
        bytes pay the link floor, the f32 expansion never crosses it."""
        if self.quant != "int4":
            return dev
        from repro.quant.int4 import dequantize_int4
        out = {}
        for name, arr in dev.items():
            if name.endswith("#q"):
                base = name[:-2]
                scale = dev[base + "#s"]
                # group size is implied by the shapes: K split into
                # K//group scale rows (scaled-down configs use smaller
                # groups than the canonical 128 — see int4_group).
                g = arr.shape[0] // scale.shape[0]
                if self.fused_int4:
                    # fused path: dequant happens inside jit on-device —
                    # XLA fuses it with the matmul (paper §3.4 kernel).
                    out[base] = _fused_dequant(arr, scale, g)
                else:
                    # unfused baseline: materialize fp32 weights first
                    out[base] = np.asarray(dequantize_int4(
                        arr, scale, jnp.float32, g))
                    out[base] = jax.device_put(out[base], self.target)
            elif name.endswith("#s"):
                continue
            else:
                out[name] = arr
        return out


@partial(jax.jit, static_argnums=(2,))
def _fused_dequant(packed, scale, group: int = 128):
    """INT4 weights decoded on-device inside jit; XLA fuses the dequant into
    the consuming matmul — the CPU emulation of the paper's fused kernel
    (on TPU the Pallas kernel in kernels/int4_matmul.py does this in VREGs)."""
    from repro.quant.int4 import dequantize_int4
    return dequantize_int4(packed, scale, jnp.float32, group)


def naive_disk_to_host(disk: DiskStore, key: str) -> np.ndarray:
    """Baseline: one fromfile() call (the PyTorch-load analogue)."""
    return disk.get(key)


def blockwise_disk_to_host(disk: DiskStore, key: str,
                           block_bytes: int = DEFAULT_BLOCK,
                           n_threads: int = 3,
                           out: Optional[np.ndarray] = None) -> np.ndarray:
    """Parallel blockwise read into a preallocated host buffer."""
    shape, dtype = disk.meta(key)
    total = int(np.prod(shape)) * np.dtype(dtype).itemsize
    if out is None:
        out = np.empty(total, np.uint8)
    blocks = [(o, min(block_bytes, total - o))
              for o in range(0, total, block_bytes)]
    if len(blocks) <= 1 or n_threads <= 1:
        disk.read_range(key, 0, total, out)
        return out.view(dtype).reshape(shape)
    with ThreadPoolExecutor(max_workers=n_threads) as ex:
        list(ex.map(lambda b: disk.read_range(key, b[0], b[1], out), blocks))
    return out.view(dtype).reshape(shape)


def host_to_device(arr: np.ndarray):
    """Synchronous host->device copy (blocks the calling thread until
    the device buffer is materialized)."""
    out = jax.device_put(arr)
    out.block_until_ready()
    return out


def pipelined_disk_to_device(disk: DiskStore, key: str,
                             block_bytes: int = DEFAULT_BLOCK,
                             n_threads: int = 3):
    """Full suite: blockwise parallel disk reads overlapped with staged
    host->device copies (Fig. 3 timeline).  The device-side buffer is
    assembled blockwise in a staging array while later disk blocks are
    still in flight, then materialized as one device array."""
    shape, dtype = disk.meta(key)
    total = int(np.prod(shape)) * np.dtype(dtype).itemsize
    host = np.empty(total, np.uint8)
    staging = np.empty(total, np.uint8)   # "pinned" staging = PCIe analogue
    blocks = [(o, min(block_bytes, total - o))
              for o in range(0, total, block_bytes)]
    done_q: queue.Queue = queue.Queue()

    def read_block(b):
        disk.read_range(key, b[0], b[1], host)
        done_q.put(b)

    with ThreadPoolExecutor(max_workers=n_threads) as ex:
        for b in blocks:
            ex.submit(read_block, b)
        copied = 0
        while copied < len(blocks):
            o, n = done_q.get()          # overlap: copy while reads continue
            staging[o:o + n] = host[o:o + n]
            copied += 1
    return host_to_device(staging.view(dtype).reshape(shape))


def sweep_block_size(disk: DiskStore, key: str, sizes=None,
                     n_threads: int = 3, repeats: int = 2):
    """Appendix-A experiment: measured bandwidth per block size."""
    import time
    sizes = sizes or [1 * 2**20, 2 * 2**20, 4 * 2**20, 8 * 2**20,
                      16 * 2**20, 32 * 2**20, 64 * 2**20]
    shape, dtype = disk.meta(key)
    total = int(np.prod(shape)) * np.dtype(dtype).itemsize
    out = []
    for bs in sizes:
        ts = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            blockwise_disk_to_host(disk, key, block_bytes=bs,
                                   n_threads=n_threads)
            ts.append(time.perf_counter() - t0)
        bw = total / min(ts)
        out.append((bs, bw))
    return out
