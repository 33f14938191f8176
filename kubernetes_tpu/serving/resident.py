"""Resident device planes: the packed used-state stays warm across cycles.

Pre-serving, every `assign()` re-uploaded the whole (N, 2R+1) int32
used-state pack (used_q ‖ used_nz_q ‖ used_pods) from the snapshot —
~1.4 MB per call at 50k nodes, paid even when one pod moved one node.
The host side stopped doing the equivalent in r13 (`SchedulerCache`
dirty-set snapshots + `ClusterTensors._init_delta` re-quantize only
changed rows); this class makes the device side match: the dirty row
set comes straight from the cache's `changed_since` log (O(changed),
never an O(N) generation walk), the rows are re-quantized from the
ClusterTensors arrays, and only they ship to the device — as a fused
scatter inside the fast-path solve (`solver.solve_one_fresh`, one
dispatch) or a standalone scatter for batch assigns
(`resident_row_scatter` below).

Refresh contract (what invalidates what — README "Online serving path"):

- **row refresh**: a node's generation moved (assume/confirm/forget,
  informer node update) → that row is re-quantized and scattered.
  Bit-identical to a full upload by construction: both read the same
  ct rows.
- **full rebuild**: the node SET changed (`set_epoch`), the resource
  columns/scales/pad changed, the snapshot carries no epoch handles /
  the changed-log window doesn't reach back (fallback: one O(N) diff),
  or the dirty set exceeds REBUILD_FRACTION of the rows (a contiguous
  upload beats a dense scatter).
- a batch solve's on-device chained state (`backend._dev_used` after
  chunks ran) never touches the resident base — jax arrays are
  immutable and the next refresh re-derives from the cache, where the
  assumes landed anyway.

The host mirror (`_pack_np` + per-row generations) is updated at
refresh() time; the DEVICE array catches up when the caller applies
the returned delta (used_pack does it inline; the fast path fuses it
into the solve and `adopt()`s the result). Un-adopted deltas persist
in `_pending` and ride the next refresh — an exception between refresh
and adopt can delay a row, never lose it.
"""

from __future__ import annotations

import functools
import time

import jax
import numpy as np

REBUILD_FRACTION = 0.25

@functools.lru_cache(maxsize=None)
def resident_row_scatter(sharding=None):
    """Jitted `pack.at[rows].set(vals)` for the resident used-state
    pack, one program per sharding. Rows/vals are tiny (the cache's
    dirty set — O(assumed pods) per cycle), so under a mesh they ride
    replicated while the (N, 2R+1) pack stays sharded over the nodes
    axis: `out_shardings` pins the result's sharding so the resident
    array never silently de-shards across refreshes (a gathered pack
    would re-pay the full-upload cost the scatter exists to avoid). On
    a single device (sharding=None) it is a plain jitted scatter."""

    def body(pack, rows, vals):
        return pack.at[rows].set(vals)

    return jax.jit(body, out_shardings=sharding)


class ResidentPlanes:
    def __init__(self, backend, metrics=None):
        self.backend = backend
        self.metrics = metrics
        self._key: tuple | None = None
        self._gen = -1
        self._gens: list | None = None
        self._pack_np: np.ndarray | None = None
        self._dev = None
        #: dirty rows whose device scatter hasn't been applied yet.
        self._pending: set[int] = set()
        #: observability (also mirrored into the metrics registry).
        self.full_rebuilds = 0
        self.row_refreshes = 0

    def invalidate(self) -> None:
        self._key = None
        self._dev = None
        self._pending.clear()

    # -- refresh ------------------------------------------------------------

    def _rebuild(self, ct) -> None:
        pack = np.concatenate(
            [ct.used_q, ct.used_nz_q,
             ct.used_pods.astype(np.int32)[:, None]], axis=1)
        self._pack_np = pack
        self._dev = self.backend._put(pack, "nodes_mat")
        self._gens = list(ct.node_gens)
        self._gen = ct.generation
        self._pending.clear()
        self.full_rebuilds += 1

    def refresh(self, ct, snapshot=None):
        """Bring the host mirror up to `ct` and return the device delta:
        None when the device array is already fresh (full rebuild, or
        nothing changed), else bucket-padded (rows, vals) the caller
        must apply — via used_pack's inline scatter or the fast path's
        fused solve followed by adopt()."""
        t0 = time.perf_counter()
        key = (ct.set_epoch, ct.n_pad, ct.n_real,
               tuple(ct.resources), tuple(ct.scales))
        out = None
        worked = False
        if self._dev is None or self._key != key or ct.set_epoch < 0:
            self._rebuild(ct)
            self._key = key
            worked = True
        else:
            changed = None
            fn = getattr(snapshot, "changed_since", None) \
                if snapshot is not None else None
            if fn is not None and self._gen >= 0:
                changed = fn(self._gen)
            if changed is None:
                # No changed-log window: one O(N) diff against the
                # mirror's per-row generations.
                changed = [i for i, g in enumerate(ct.node_gens)
                           if self._gens[i] != g]
            if len(changed) + len(self._pending) \
                    > REBUILD_FRACTION * max(ct.n_real, 1):
                self._rebuild(ct)
                worked = True
            else:
                self._gen = ct.generation
                fresh = [i for i in changed
                         if i < ct.n_real and self._gens[i]
                         != ct.node_gens[i]]
                for i in fresh:
                    self._gens[i] = ct.node_gens[i]
                self._pending.update(fresh)
                if self._pending:
                    idxs = np.fromiter(sorted(self._pending), np.int32,
                                       count=len(self._pending))
                    vals = np.concatenate(
                        [ct.used_q[idxs], ct.used_nz_q[idxs],
                         ct.used_pods[idxs].astype(np.int32)[:, None]],
                        axis=1)
                    self._pack_np[idxs] = vals
                    self.row_refreshes += 1
                    out = self._pad_bucket(idxs, vals)
                    worked = True
        if worked and self.metrics is not None:
            # No-op refreshes (nothing dirty) deliberately don't count:
            # the counter/histogram describe actual rebuild/scatter
            # work, and diluting them with no-op walls would misstate
            # the refresh cost the detail JSON reports.
            self.metrics.resident_plane_refreshes.inc()
            self.metrics.resident_plane_refresh.observe(
                time.perf_counter() - t0)
        return out

    @staticmethod
    def _pad_bucket(rows: np.ndarray, vals: np.ndarray):
        """Pad the delta to a power-of-two bucket (repeating the first
        row — the duplicate set is idempotent) so the jitted scatter /
        fused solve compiles once per bucket, not per dirty-set size."""
        cap = 1
        while cap < len(rows):
            cap <<= 1
        if cap > len(rows):
            pad = cap - len(rows)
            rows = np.concatenate(
                [rows, np.full((pad,), rows[0], np.int32)])
            vals = np.concatenate(
                [vals, np.repeat(vals[:1], pad, axis=0)])
        return rows, vals

    def adopt(self, dev) -> None:
        """Install a device pack that already includes every pending
        row (the fused fast-path solve returns it)."""
        self._dev = dev
        self._pending.clear()

    def apply_delta(self, delta) -> None:
        """Apply a refresh() delta via the standalone scatter (a tiny
        program — per-bucket compiles are cheap, unlike the fused
        solve's) and adopt the result."""
        fn = resident_row_scatter(
            getattr(self.backend, "_sh_nodes_mat", None))
        self.adopt(fn(self._dev, delta[0], delta[1]))

    def used_pack(self, ct, snapshot=None):
        """The refreshed device pack (the batch path's entry point):
        refresh, apply any delta via the standalone scatter, return."""
        delta = self.refresh(ct, snapshot)
        if delta is not None:
            self.apply_delta(delta)
        return self._dev

    # -- test/debug hooks ---------------------------------------------------

    def host_mirror(self) -> np.ndarray | None:
        """The host copy of the resident pack (None before first use)."""
        return self._pack_np
