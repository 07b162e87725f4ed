"""Simulated persistent-memory device with explicit volatility semantics.

The paper's correctness arguments all rest on three hardware facts about
PMEM (Optane DCPMM behind the x86 cache hierarchy):

  1. Stores are *volatile* until the cache line has been written back
     (clwb/clflushopt) and a fence has retired (sfence).
  2. Persistence granularity/atomicity is 8 bytes: on power loss an
     in-flight cache-line writeback may tear at any 8-byte boundary, and
     dirty lines may reach the media in *any order* (implicit evictions).
  3. Media errors / stray writes can silently corrupt persisted bytes.

``PMEMDevice`` models exactly these semantics so crash-consistency can be
*property-tested* rather than asserted.  Two modes:

  * ``strict``  — full volatile-overlay model at 8-byte granularity.
                  ``crash()`` keeps an arbitrary subset of unflushed units
                  (torn + reordered writes).  Used by correctness tests.
  * ``fast``    — writes go straight to a NumPy buffer (a write-through
                  view of the same semantics: everything a crash *may*
                  persist).  Used where only the software cost (copies,
                  checksums, locking) matters.

The strict model is vectorized (DESIGN.md §1): instead of a dict of
8-byte unit blobs and Python sets of line numbers, the device keeps

  * ``_overlay``  — a full-size uint8 ndarray holding the newest (not yet
                    persisted) bytes; only valid where ``_dirty`` is set,
  * ``_dirty``    — one bool per 8-byte unit (the torn-write granule),
  * ``_resident`` — one bool per cache line (the Fig. 6 LLC model),

so ``write``/``read``/``persist``/``crash`` are slice assignments and
boolean-mask copies.  A dirty unit's overlay content is always the *full*
unit (partial stores are seeded from the durable image first), which is
what makes ``crash()`` an independent keep/drop draw per unit — the same
torn/reordered semantics the scalar model realized one dict entry at a
time.

``DeviceStats`` counts the hardware events the paper reads with PCM
(stores, flushes, lines written back, fences, LLC hits and misses of
NIC reads); the flush-economy claims are pinned on these counts.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Optional

import numpy as np

CACHE_LINE = 64  # bytes, x86
ATOM = 8         # PMEM atomic persist unit, bytes


@dataclass
class DeviceStats:
    """Observable hardware-event counters (the paper reads these via PCM)."""

    writes: int = 0
    bytes_written: int = 0
    flushes: int = 0
    lines_flushed: int = 0
    fences: int = 0
    llc_misses: int = 0          # lines read by DMA that were not cache-resident
    llc_hits: int = 0
    media_errors_injected: int = 0

    def snapshot(self) -> "DeviceStats":
        return DeviceStats(**self.__dict__)


class PMEMDevice:
    """A byte-addressable persistent memory device (one DAX-mapped file)."""

    def __init__(
        self,
        size: int,
        mode: str = "fast",
        name: str = "pmem0",
    ):
        if mode not in ("fast", "strict"):
            raise ValueError(f"unknown mode {mode!r}")
        self.size = int(size)
        self.mode = mode
        self.name = name
        self.stats = DeviceStats()
        self._lock = threading.Lock()
        # Durable image: what survives power loss *for sure*.
        self._durable = np.zeros(self.size, dtype=np.uint8)
        self._n_units = (self.size + ATOM - 1) // ATOM
        self._n_lines = (self.size + CACHE_LINE - 1) // CACHE_LINE
        # Cache-residency of lines (True while dirty in LLC).  Used for the
        # Fig. 6 effect: flushing evicts lines, so a subsequent NIC DMA read
        # misses LLC and must re-read from PMEM.  (clwb was implemented as an
        # evicting flush on the paper's CPUs — footnote 5.)
        self._resident = np.zeros(self._n_lines, dtype=bool)
        if mode == "strict":
            # Volatile overlay: newest bytes, valid only where _dirty is set.
            self._overlay = np.zeros(self.size, dtype=np.uint8)
            self._dirty = np.zeros(self._n_units, dtype=bool)
        else:
            self._overlay = None
            self._dirty = None
        self._dirty_count = 0

    # ------------------------------------------------------------------ #
    # store / load
    # ------------------------------------------------------------------ #
    def write(self, off: int,
              data: bytes | bytearray | memoryview | np.ndarray) -> None:
        """CPU stores to [off, off+len). Volatile until persisted."""
        arr = _as_array(data)
        n = arr.size
        self._check(off, n)
        if n == 0:
            with self._lock:
                self.stats.writes += 1
            return
        if self.mode == "fast":
            self._durable[off : off + n] = arr
            with self._lock:
                self.stats.writes += 1
                self.stats.bytes_written += n
                self._resident[off // CACHE_LINE : (off + n - 1) // CACHE_LINE + 1] = True
        else:
            with self._lock:
                self._write_strict_locked(off, arr)
                self.stats.writes += 1
                self.stats.bytes_written += n
                self._resident[off // CACHE_LINE : (off + n - 1) // CACHE_LINE + 1] = True

    def _write_strict_locked(self, off: int, arr: np.ndarray) -> None:
        """Store into the overlay at 8-byte-unit granularity.

        Boundary units that the store only partially covers are seeded
        from the newest visible content first, so every dirty unit's
        overlay slice is the complete unit — the invariant ``crash()``
        and ``persist()`` rely on.
        """
        n = arr.size
        u0 = off // ATOM
        u1 = (off + n - 1) // ATOM + 1
        dirty = self._dirty
        if off % ATOM and not dirty[u0]:
            s = u0 * ATOM
            e = min(s + ATOM, self.size)
            self._overlay[s:e] = self._durable[s:e]
        if (off + n) % ATOM and not dirty[u1 - 1]:
            s = (u1 - 1) * ATOM
            e = min(s + ATOM, self.size)
            self._overlay[s:e] = self._durable[s:e]
        self._overlay[off : off + n] = arr
        dslice = dirty[u0:u1]
        self._dirty_count += int(dslice.size - np.count_nonzero(dslice))
        dslice[:] = True

    def read(self, off: int, n: int) -> bytes:
        """CPU load: sees the newest (volatile-overlaid) data."""
        self._check(off, n)
        if self.mode == "fast" or self._dirty_count == 0 or n == 0:
            return self._durable[off : off + n].tobytes()
        with self._lock:
            u0 = off // ATOM
            u1 = (off + n - 1) // ATOM + 1
            dslice = self._dirty[u0:u1]
            if not dslice.any():
                return self._durable[off : off + n].tobytes()
            out = self._durable[off : off + n].copy()
            mask = np.repeat(dslice, ATOM)[off - u0 * ATOM : off - u0 * ATOM + n]
            np.copyto(out, self._overlay[off : off + n], where=mask)
            return out.tobytes()

    def view(self, off: int, n: int) -> Optional[memoryview]:
        """Direct load/store pointer into PMEM (the paper's reserve() returns
        one).  Only available in fast mode; strict mode callers fall back to
        ``write``/``read`` so the volatility model stays sound."""
        self._check(off, n)
        if self.mode == "fast":
            return self._durable[off : off + n].data
        return None

    # ------------------------------------------------------------------ #
    # persistence primitive (clwb loop + sfence)
    # ------------------------------------------------------------------ #
    def persist(self, off: int, n: int) -> None:
        """Guarantee [off, off+n) is durable (clwb per line + sfence).

        Evicts the lines from the cache model (see _resident note).  Every
        8-byte unit *overlapping* the range is persisted whole (a clwb
        flushes full lines; the scalar model did the same).
        """
        self._check(off, n)
        with self._lock:
            if self.mode == "strict" and n > 0 and self._dirty_count:
                u0 = off // ATOM
                u1 = (off + n - 1) // ATOM + 1
                dslice = self._dirty[u0:u1]
                ndirty = int(np.count_nonzero(dslice))
                if ndirty:
                    s = u0 * ATOM
                    e = min(u1 * ATOM, self.size)
                    mask = np.repeat(dslice, ATOM)[: e - s]
                    np.copyto(self._durable[s:e], self._overlay[s:e],
                              where=mask)
                    self._dirty_count -= ndirty
                    dslice[:] = False
            if n > 0:
                l0 = off // CACHE_LINE
                l1 = (off + n - 1) // CACHE_LINE + 1
                rslice = self._resident[l0:l1]
                dirty_lines = int(np.count_nonzero(rslice))
                rslice[:] = False
            else:
                dirty_lines = 0
            self.stats.flushes += 1
            self.stats.lines_flushed += dirty_lines
            self.stats.fences += 1

    def dma_read(self, off: int, n: int) -> bytes:
        """Device-side (NIC) read of the *newest* data, as an RDMA HCA would
        snoop it.  Counts LLC residency: lines evicted by a prior flush
        miss and must be re-read from PMEM (the Fig. 6 effect)."""
        data = self.read(off, n)
        if n > 0:
            l0 = off // CACHE_LINE
            l1 = (off + n - 1) // CACHE_LINE + 1
            with self._lock:
                hit = int(np.count_nonzero(self._resident[l0:l1]))
                self.stats.llc_misses += l1 - l0 - hit
                self.stats.llc_hits += hit
        return data

    # ------------------------------------------------------------------ #
    # failure injection
    # ------------------------------------------------------------------ #
    def crash(self, rng: Optional[np.random.Generator] = None,
              keep_probability: float = 0.5) -> "PMEMDevice":
        """Power loss.  Returns the device as found at next boot.

        Every unflushed 8-byte unit independently either reached the media
        (implicit eviction happened before the crash) or is lost — this
        realizes both *torn writes* (a record's units split) and *reordered
        persistence* (later stores survive while earlier ones vanish).
        """
        rng = rng or np.random.default_rng(0)
        survivor = PMEMDevice(self.size, mode=self.mode, name=self.name)
        with self._lock:
            survivor._durable[:] = self._durable
            if self.mode == "strict" and self._dirty_count:
                units = np.flatnonzero(self._dirty)
                kept = units[rng.random(units.size) < keep_probability]
                if kept.size:
                    mask_units = np.zeros(self._n_units, dtype=bool)
                    mask_units[kept] = True
                    bmask = np.repeat(mask_units, ATOM)[: self.size]
                    np.copyto(survivor._durable, self._overlay, where=bmask)
        return survivor

    def corrupt(self, off: int, n: int, rng: Optional[np.random.Generator] = None,
                nbits: int = 8) -> None:
        """Inject an undetected media error: flip bits in the durable image."""
        self._check(off, n)
        rng = rng or np.random.default_rng(0)
        with self._lock:
            for _ in range(nbits):
                pos = off + int(rng.integers(0, n))
                self._durable[pos] ^= np.uint8(1 << int(rng.integers(0, 8)))
            self.stats.media_errors_injected += 1

    # ------------------------------------------------------------------ #
    def dirty_units(self) -> int:
        with self._lock:
            return self._dirty_count

    def _check(self, off: int, n: int) -> None:
        if off < 0 or n < 0 or off + n > self.size:
            raise ValueError(
                f"access [{off}, {off + n}) out of bounds for {self.name} "
                f"(size {self.size})"
            )

    def __repr__(self) -> str:  # pragma: no cover
        return (f"PMEMDevice({self.name}, size={self.size}, mode={self.mode}, "
                f"dirty_units={self.dirty_units()})")


def _as_array(data) -> np.ndarray:
    if isinstance(data, np.ndarray):
        return np.ascontiguousarray(data).view(np.uint8).reshape(-1)
    return np.frombuffer(data, dtype=np.uint8)
