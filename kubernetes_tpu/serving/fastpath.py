"""Pinned single-pod fast path: one pre-compiled C=1 solve per placement.

The batch pipeline's per-pod cost is amortization — chunk build, class
interning, shortlist prefilter, multistart permutations — none of which
a lone pod can use; pre-serving, the scheduler routed lone pods to the
per-pod HOST path instead (a full O(N·plugins) Python scan: the r15
trickle row's 3.8 ms p50). This path is the third shape: the pod's
equivalence-class row solves against the RESIDENT device planes through
`ops/solver.solve_one` — the exact kernel composition of the fused
chunk program's first scan step (same kernels, same order, same dtypes,
same argmax tie rule), so assignments are bit-identical to the batch
path by construction (tests/test_serving_smoke.py pins it with a
randomized differential).

Eligibility (README "Online serving path" documents the contract): a
pod takes the fast path only when every plugin influence on its
placement is representable in the resident planes —

- requests covered by the tracked resource columns;
- no nominated node (preemptor retries keep their nominee-first check);
- static-row plugins (NodeAffinity/NodeName/NodeUnschedulable) allowed:
  their signature-cached rows AND into the pod's base mask (a NodeName
  pin is just a one-column mask here — a lone pod's argmax over ≤1
  column cannot be moved by score normalization, so the batch path's
  exception-column form is assignment-identical);
- every stateful filter/score gate inactive (no affinity terms against
  a term-free cluster, no spread constraints, no ports/volumes/claims,
  no NRT/DRA activity) — the same `_FILTER_ACTIVE`/`_SCORE_ACTIVE`
  gates the chunk prep consults, so "gate says the plugin would Skip"
  means exactly what it means there;
- no nonzero host score rows (preferred node affinity, image locality
  against image-bearing nodes) — score normalization is feasible-set
  relative and belongs to the chunk prep;
- no gang membership (Coscheduling atomicity needs the batch solver).

Anything else falls through to the batch path (a lone pod as a batch
of one; the host path only where the backend cannot take the pod), which
also owns diagnostics/preemption for no-fit pods — the fast path only
takes the happy path, and a host verify (exact integer re-check)
backstops the quantized device fit exactly like the batch verify.
"""

from __future__ import annotations

import logging

import numpy as np

from kubernetes_tpu.ops import solver
from kubernetes_tpu.ops.backend import (
    DEVICE_FILTER_PLUGINS,
    DEVICE_SCORE_PLUGINS,
    STATIC_ROW_PLUGINS,
    STATIC_SCORE_PLUGINS,
    _FILTER_ACTIVE,
    _SCORE_ACTIVE,
)
from kubernetes_tpu.scheduler.plugins.noderesources import (
    insufficient_resources,
)
from kubernetes_tpu.utils.locking import check_dispatch_seam

logger = logging.getLogger(__name__)

#: Largest refresh delta the solve fuses (solve_one_fresh): each bucket
#: size is a separate jit signature of the FULL solve program, so only
#: the steady-state buckets stay fused — between consecutive lone-pod
#: placements exactly one node changes (the previous assume, plus its
#: bind confirmation on the same row), occasionally two. Bigger deltas
#: (the first solve after a batch dispatch dirtied a chunk's worth of
#: rows) apply through the standalone scatter — a tiny program whose
#: per-bucket compiles are cheap — and solve un-fused. Without this
#: split, every novel bucket recompiled the whole solve mid-serve and
#: the compile walls poisoned the tier's fast-wall estimate.
FUSE_MAX_ROWS = 2


class SinglePodFastPath:
    def __init__(self, backend, resident, metrics=None):
        self.backend = backend
        self.resident = resident
        self.metrics = metrics
        #: (taint-table id, scales, req/tol signature) -> packed
        #: (2R+tf+tp,) int32 class row (the solve_one req_pack).
        self._req_cache: dict[tuple, np.ndarray] = {}
        #: (row ids, n_pad) -> device bit-packed base mask; invalidated
        #: with the backend row cache (same static fingerprint).
        self._mask_cache: dict[tuple, object] = {}
        self._mask_fp: tuple | None = None
        #: resident all-true mask / zero score rows per plane shape.
        self._alltrue: dict[tuple, object] = {}
        self._zero_scores: dict[int, object] = {}
        #: introspection counters (the serving tier also mirrors the
        #: success count into the metrics registry).
        self.placed = 0
        self.ineligible = 0
        self.no_fit = 0
        #: every program variant compiled (warm() completed) — the
        #: serving tier retries warm-up until a usable donor pod
        #: appears, so this flips exactly once per cluster shape.
        self.warmed = False

    # -- eligibility --------------------------------------------------------

    def _base_rows(self, pi, snapshot, fwk, ct) -> list | None:
        """The pod's host filter rows (static plugins only), or None when
        any plugin outside the fast path's vocabulary is live for it."""
        rows = []
        for plugin in fwk.filter_plugins:
            name = plugin.NAME
            if name in DEVICE_FILTER_PLUGINS:
                continue
            if name in STATIC_ROW_PLUGINS:
                row, all_true = self.backend._static_filter_row(
                    plugin, pi, snapshot, ct)
                if not all_true:
                    rows.append(row)
                continue
            gate = _FILTER_ACTIVE.get(name)
            if gate is None or gate(plugin, pi, snapshot):
                return None
        for plugin in fwk.score_plugins:
            name = plugin.NAME
            if name in DEVICE_SCORE_PLUGINS:
                continue
            if name in STATIC_SCORE_PLUGINS:
                if name == "NodeAffinity":
                    if ((pi.affinity.get("nodeAffinity") or {}).get(
                            "preferredDuringSchedulingIgnoredDuringExecution")):
                        return None
                    continue
                _, any_nonzero = self.backend._static_score_row(
                    plugin, pi, snapshot, ct)
                if any_nonzero:
                    return None
                continue
            gate = _SCORE_ACTIVE.get(name)
            if gate is None or gate(plugin, pi, snapshot):
                return None
        cosched = next(
            (pl for pl in fwk.plugins if pl.NAME == "Coscheduling"), None)
        if cosched is not None and cosched.group_key(pi):
            return None
        return rows

    # -- device inputs ------------------------------------------------------

    def _req_pack(self, pi, ct):
        """DEVICE-cached (2R+tf+tp,) class row for the pod's request /
        toleration signature — template pods hit this every solve, so
        the upload happens once per signature, not per placement. The
        cache is cleared with the static fingerprint (in _base_mask):
        the taint table rebuilds exactly when the fingerprint moves, so
        no table identity belongs in the key (an id() there could match
        a recycled address and serve stale untolerated masks)."""
        key = (tuple(ct.scales), tuple(ct.resources),
               repr(pi.requests), repr(pi.nonzero_requests),
               repr(pi.tolerations))
        pack = self._req_cache.get(key)
        if pack is None:
            if len(self._req_cache) > 4096:
                self._req_cache.clear()
            q, qnz = ct.quantize_requests(pi.requests, pi.nonzero_requests)
            uf = ct.taints.untolerated(pi.tolerations, "filter")
            up = ct.taints.untolerated(pi.tolerations, "prefer")
            pack = self.backend._put(np.concatenate(
                [q, qnz, uf.astype(np.int32), up.astype(np.int32)]))
            self._req_cache[key] = pack
        return pack

    def _base_mask(self, rows, ct):
        """Device bit-packed base mask for the pod's host-row set: the
        resident all-true plane for the (overwhelmingly common) empty
        set, one cached upload per distinct row set otherwise."""
        if self._mask_fp != ct._static_fp:
            # Static fingerprint moved (cordon, taint edit, node churn):
            # the backend row cache just reset, and row identities with
            # it — the masks derived from them are stale too, as are
            # the req packs (their untolerated vectors were built
            # against the previous taint table).
            self._mask_cache.clear()
            self._alltrue.clear()
            self._req_cache.clear()
            self._mask_fp = ct._static_fp
        if not rows:
            key = (ct.n_pad, ct.n_real)
            dev = self._alltrue.get(key)
            if dev is None:
                m = np.zeros((ct.n_pad,), dtype=np.bool_)
                m[: ct.n_real] = True
                # Replicated: N/8 bytes — smaller than any sharding win.
                dev = self._alltrue[key] = self.backend._put(np.packbits(m))
            return dev
        key = tuple(id(r) for r in rows) + (ct.n_pad,)
        dev = self._mask_cache.get(key)
        if dev is None:
            if len(self._mask_cache) > 1024:
                self._mask_cache.clear()
            m = np.zeros((ct.n_pad,), dtype=np.bool_)
            m[: ct.n_real] = True
            for r in rows:
                m[: ct.n_real] &= r
            dev = self._mask_cache[key] = self.backend._put(np.packbits(m))
        return dev

    def _zero_score_row(self, ct):
        dev = self._zero_scores.get(ct.n_pad)
        if dev is None:
            # f16 like the batch wire's clean score plane (cast to f32 on
            # device in both paths — zeros are exact either way).
            dev = self._zero_scores[ct.n_pad] = self.backend._put(
                np.zeros((ct.n_pad,), dtype=np.float16), "nodes_vec")
        return dev

    # -- the solve ----------------------------------------------------------

    def try_schedule(self, pi, snapshot, fwk, record: bool = True) -> str | None:
        """One placement attempt. Returns the node name, or None when the
        pod is ineligible / nothing fits (the caller routes it through
        the normal path, which owns diagnostics and preemption).
        record=False is the warmup form: full solve, nothing counted
        (the caller discards the result without assuming)."""
        backend = self.backend
        ct = backend._tensors(snapshot)
        if pi.nominated_node or ct.has_unknown_resource(pi.requests):
            self.ineligible += 1
            return None
        rows = self._base_rows(pi, snapshot, fwk, ct)
        if rows is None:
            self.ineligible += 1
            return None
        params = backend._fwk_params(fwk, ct)
        static = backend.ensure_static(ct)
        tail = (self._base_mask(rows, ct), self._zero_score_row(ct),
                self._req_pack(pi, ct),
                params["fit_col_w"], params["bal_col_mask"],
                params["shape_u"], params["shape_s"],
                params["w_fit"], params["w_bal"], params["w_taint"],
                params["taint_filter_on"], params["strategy"])
        delta = self.resident.refresh(ct, snapshot)
        if delta is not None and len(delta[0]) > FUSE_MAX_ROWS:
            self.resident.apply_delta(delta)
            delta = None
        if delta is None:
            idx_d = solver.solve_one(
                static["alloc_q"], self.resident._dev,
                static["alloc_pods"], static["taint_f"],
                static["taint_p"], *tail)
        else:
            # Fused refresh+solve: one dispatch applies the dirty rows
            # and solves; the refreshed pack becomes the resident base.
            idx_d, pack = solver.solve_one_fresh(
                static["alloc_q"], self.resident._dev,
                delta[0], delta[1], static["alloc_pods"],
                static["taint_f"], static["taint_p"], *tail)
            self.resident.adopt(pack)
        check_dispatch_seam("serving.fastpath.fetch")
        idx = int(np.asarray(idx_d))
        if idx < 0 or idx >= ct.n_real:
            self.no_fit += 1
            return None
        name = ct.node_names[idx]
        ni = snapshot.get(name)
        if ni is None or insufficient_resources(pi, ni):
            # Quantized fit is conservative, so this is belt-and-braces:
            # route the pod through the exact batch verify instead.
            logger.warning(
                "fast path verify rejected %s on %s; rerouting", pi.key,
                name)
            self.no_fit += 1
            return None
        if record:
            self.placed += 1
            if self.metrics is not None:
                self.metrics.serving_fast_path_pods.inc()
        return name

    def warm(self, pi, snapshot, fwk) -> None:
        """Compile every serve-path program variant OFF the serve path:
        the plain solve and both fused refresh buckets (idempotent
        deltas — row 0 set to its current value). Called by the serving
        tier during its first batch dispatch so no measured lone-pod
        placement ever pays a jit. Deliberately compiles even when the
        warm pod itself has NO FIT (a failure-wave pod is a perfectly
        good shape donor) — bailing there once left the fused buckets
        cold, and their mid-serve compiles poisoned the tier's wall
        estimate."""
        backend = self.backend
        ct = backend._tensors(snapshot)
        if ct.n_real < 1 or ct.has_unknown_resource(pi.requests):
            return
        rows = self._base_rows(pi, snapshot, fwk, ct)
        if rows is None:
            return
        params = backend._fwk_params(fwk, ct)
        static = backend.ensure_static(ct)
        res = self.resident
        res.used_pack(ct, snapshot)  # ensure base + drain any pending
        tail = (self._base_mask(rows, ct), self._zero_score_row(ct),
                self._req_pack(pi, ct),
                params["fit_col_w"], params["bal_col_mask"],
                params["shape_u"], params["shape_s"],
                params["w_fit"], params["w_bal"], params["w_taint"],
                params["taint_filter_on"], params["strategy"])
        idx_d = solver.solve_one(
            static["alloc_q"], res._dev, static["alloc_pods"],
            static["taint_f"], static["taint_p"], *tail)
        np.asarray(idx_d)  # block: the compile finishes inside warmup
        for b in range(1, FUSE_MAX_ROWS + 1):
            idx_rows = np.zeros((b,), np.int32)
            vals = np.repeat(res._pack_np[:1], b, axis=0)
            _idx, pack = solver.solve_one_fresh(
                static["alloc_q"], res._dev, idx_rows, vals,
                static["alloc_pods"], static["taint_f"],
                static["taint_p"], *tail)
            res.adopt(pack)
        self.warmed = True
