"""Device tier: capacity-bounded payload buffer over the hot-node cache.

The ``DoubleBufferedCache`` tracks hot node *ids*; this tier holds the
actual feature payload rows for the active buffer (what the accelerator
keeps in device memory) and serves the hit path through the
``kernels.embedding_bag`` Pallas row gather — an exact gather, so the
kernel output is bit-equal to a plain ``table[idx]`` (asserted by the
parity tests).

The gather pads the request length to the next power of two so the jitted
kernel compiles once per size bucket instead of once per distinct batch
length; the payload table itself is zero-padded to the cache capacity (and
its width to the kernel's 128-lane layout) so its shape is static for the
whole run. The kernel compiles on TPU and runs in the Pallas interpreter
only on CPU (``segment_mm.default_interpret``).
"""
from __future__ import annotations

import numpy as np

from repro.core.windowed_cache import DoubleBufferedCache, RebuildPlan
from repro.kernels.embedding_bag import gather_layout, gather_rows


class DevicePayloadTier:
    """Payload rows for the cache's active buffer + kernel-served hit path."""

    def __init__(self, cache: DoubleBufferedCache, n_feat: int,
                 dtype=np.float32):
        self.cache = cache
        self.n_feat = int(n_feat)
        self.dtype = np.dtype(dtype)
        self.capacity = int(cache.capacity)
        self._payload = np.zeros((0, self.n_feat), self.dtype)
        self._table = None          # device (capacity, 1, D_pad) gather layout
        self.n_loads = 0
        self.rows_gathered = 0

    @property
    def resident_bytes(self) -> float:
        return float(self._payload.nbytes)

    # ---------------------------------------------------------------- loads
    def load(self, plan: RebuildPlan, peek_fn,
             fetched_rows: np.ndarray | None = None) -> None:
        """Assemble the payload for ``plan.hot_nodes``.

        MUST run before ``cache.swap(plan)``: persisted rows are copied out
        of the current payload via the *old* active-node table (the O(1)
        pointer-flip story — persisted rows never leave the device).
        ``fetched_rows`` are the remotely-fetched rows for
        ``plan.hot_nodes[plan.fetched]`` when the builder already gathered
        them; otherwise they are peeked from the backing store.
        """
        ids = plan.hot_nodes
        new_payload = np.zeros((len(ids), self.n_feat), self.dtype)
        old_active = self.cache.active_nodes
        if plan.persisted.any() and len(old_active) == len(self._payload):
            kept = ids[plan.persisted]
            pos = np.searchsorted(old_active, kept)
            new_payload[plan.persisted] = self._payload[pos]
        if plan.fetched.any():
            if fetched_rows is None:
                fetched_rows = peek_fn(ids[plan.fetched])
            new_payload[plan.fetched] = np.asarray(
                fetched_rows, self.dtype
            )[: int(plan.fetched.sum())]
        self._payload = new_payload
        self._table = None  # padded device view rebuilt lazily on first hit
        self.n_loads += 1

    # --------------------------------------------------------------- gather
    def gather_slots(self, slot_idx: np.ndarray) -> np.ndarray:
        """Rows for active-buffer slots via the embedding_bag kernel."""
        n = len(slot_idx)
        if n == 0 or len(self._payload) == 0:
            return np.zeros((0, self.n_feat), self.dtype)
        if self._table is None:
            padded = np.zeros((self.capacity, self.n_feat), self.dtype)
            padded[: len(self._payload)] = self._payload
            self._table = gather_layout(padded)
        bucket = 1 << (n - 1).bit_length()
        idx = np.zeros(bucket, np.int32)  # pad lookups read slot 0, cut below
        idx[:n] = np.asarray(slot_idx, np.int32)
        out = gather_rows(self._table, idx, self.n_feat)
        self.rows_gathered += n
        return np.asarray(out)[:n].astype(self.dtype)

    def gather(self, remote_ids: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """(hit_mask, rows for the hits) for a batch of remote node ids."""
        hit, slots = self.cache.lookup(remote_ids)
        return hit, self.gather_slots(slots[hit])
