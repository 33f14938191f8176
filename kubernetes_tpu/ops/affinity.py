"""Tensorized InterPodAffinity filter (BASELINE config #2 hot path).

Replaces the host plugin's O(pods × nodes × terms) Python walk
(pkg/scheduler/framework/plugins/interpodaffinity/filtering.go — "the
classic hot spot", SURVEY §2.3) with dense algebra over interned label
signatures (ops/labelsets.py):

    counts_t (N,)  = node_sig_count @ match_vec(term)        # matvec
    D_t (K,)       = segment_sum(counts_t · has_key, domains) # per-domain
    per_node (N,)  = D_t[domain_ids]                          # gather
    anti mask      = ¬has_key ∨ (per_node == 0)
    affinity mask  = has_key ∧ (per_node > 0)   [+ first-pod-in-group rule]
    symmetry mask  = ¬has_key ∨ (forbidden-domain count == 0), applied to
                     pods the resident term matches

Numpy, deliberately: U (label signatures) and T (unique terms) are tiny for
template-derived workloads, so per-term cost is a (N×U) matvec — far below
one device dispatch. A pod's result is one (N,) row, cached per pod content
signature; the backend interns the distinct rows of a chunk and ships them
as rows of its (C, N/8) class mask plane (ops/backend._prep_chunk), never a
(P, N) mask. Parity with the host plugin is differential-tested
(tests/test_affinity_tensor.py).

namespaceSelector terms COMPILE like everything else: the term's
effective namespace set resolves at table-build time
(interpodaffinity.resolve_term_namespaces) — through the plugin's
NamespaceResolver when one is wired (the reference's PreFilter namespace
merge), else statically ({} = ALL_NAMESPACES, non-empty selectors match
their explicit namespaces only, exactly what an informer-less resolver
resolves). Either way the result is just a (possibly wildcard) namespace
tuple in the interned-count keys, so no term shape routes a pod off the
tensor path; `supported()` is always True.
"""

from __future__ import annotations

import numpy as np

from kubernetes_tpu.api.labels import from_label_selector, ns_contains
from kubernetes_tpu.ops.labelsets import (
    LabelSigTable,
    TopologyTable,
    came_and_gone,
)
from kubernetes_tpu.scheduler.plugins.interpodaffinity import (
    resolve_term_namespaces as _term_ns,
)
from kubernetes_tpu.scheduler.types import PodInfo, Snapshot


def _seg_sum(values: np.ndarray, ids: np.ndarray, num: int) -> np.ndarray:
    out = np.zeros((num,), dtype=values.dtype)
    np.add.at(out, ids, values)
    return out


class _Carriers(dict):
    """The residents that carry each term of their own, by node: term
    signature -> (carrier vector over nodes, term, owner_ns[, is_hard]).
    A key lives while some resident carries its term, so the keys are
    those of a table built anew from the residents there are."""

    def __init__(self, n_pad: int):
        super().__init__()
        self._n_pad = n_pad
        #: key -> how many resident terms carry it
        self._carrying: dict[str, int] = {}

    def move(self, key: str, held: tuple, n: int, weight: float,
             step: int) -> None:
        """One resident term more (`step` 1) or fewer (-1) at node `n`."""
        got = self.get(key)
        if got is None:
            got = self[key] = (
                np.zeros((self._n_pad,), dtype=np.float32), *held)
            self._carrying[key] = 0
        got[0][n] += step * weight
        self._carrying[key] += step
        if not self._carrying[key]:
            del self[key], self._carrying[key]

    def terms(self) -> int:
        """How many resident terms the carriers hold in all."""
        return sum(self._carrying.values())


class AffinityCompiler:
    """Compiled state for batched affinity filtering, kept ACROSS snapshots.

    Built by one walk over the snapshot's resident pods, then `advance`d
    from generation to generation by the scheduler cache's delta handles
    (`set_epoch`, `spec_seq`, `changed_since` — the rule and the fall-back
    of tensorize.ClusterTensors._init_delta). Only the nodes that changed
    are read again: their label-signature rows are recounted, and the
    carriers of residents' own terms (`resident_anti`, `resident_score`)
    are moved by the pods that came to or left those nodes' two
    term-carrying lists — both by `labelsets.came_and_gone`. What reads
    node labels alone is kept too; only what was derived from pod COUNTS
    (matching counts, term masks, whole rows) is dropped. An advanced
    compiler answers exactly as one built anew on the same snapshot
    would: carrier vectors hold sums of small integers in float32, so
    neither the order pods arrived in nor the order of the keys moves a
    row by a bit.

    `ns_resolver` (plugins.interpodaffinity.NamespaceResolver) resolves
    namespaceSelector terms against live Namespace labels; without one
    the static resolution of resolve_term_namespaces applies ({} = every
    namespace, non-empty selectors = explicit namespaces only). Every
    term shape compiles — there is no host-fallback routing here."""

    def __init__(self, snapshot: Snapshot, n_pad: int, ns_resolver=None):
        self.ns_resolver = ns_resolver
        #: the resolver's epoch the resolved namespace sets belong to
        self.ns_epoch = self._resolver_epoch()
        self.n_pad = n_pad
        self.n_real = len(snapshot.nodes)
        self.sigs = LabelSigTable(snapshot, n_pad)
        self.topo = TopologyTable(snapshot.nodes, n_pad)
        #: per-term-signature compiled masks; the "elig/" rows read node
        #: labels and taints alone and survive an advance
        self._mask_cache: dict[str, np.ndarray] = {}
        #: Resident pods' required anti-affinity terms (symmetry source):
        #: term signature -> (carrier-count vector over nodes, term,
        #: owner_ns).
        self.resident_anti = _Carriers(n_pad)
        #: Resident pods' PREFERRED terms + required-affinity terms (score
        #: symmetry sources — scoring.go's second loop): term signature →
        #: (weight-summed carrier vector over nodes, term, owner_ns,
        #: is_hard). Preferred anti-affinity carriers get negative
        #: weights; hardPodAffinityWeight multiplies at score_row time.
        self.resident_score = _Carriers(n_pad)
        #: per node, the list of term-carrying pods each of the two was
        #: last read from
        self._read_anti: list[list[PodInfo]] = [[]] * self.n_real
        self._read_score: list[list[PodInfo]] = [[]] * self.n_real
        #: how this compiler reached the snapshot it points at ("full":
        #: built anew, "delta": advanced, "kept": asked again at the same
        #: snapshot); the resident term-carrying pods that step looked at
        #: (the lengths of the two lists of every node it read: a pod on
        #: both counts twice), and how many of them came and went
        self.reached = "full"
        self._point_at(snapshot)
        self._reread(range(self.n_real))
        self._drop_counted()

    def _resolver_epoch(self) -> int:
        return self.ns_resolver.epoch if self.ns_resolver is not None else -1

    def _point_at(self, snapshot: Snapshot) -> None:
        self.snapshot = snapshot
        self.generation = snapshot.generation
        self.set_epoch = snapshot.set_epoch
        self.spec_seq = snapshot.spec_seq
        self.topo.nodes = snapshot.nodes

    def release(self) -> None:
        """Let go of the snapshot pointed at, once the cache has moved on
        (its replaced node clones would live as long as this compiler
        waits for the next pod that needs it). The counts stay; `advance`
        points at the next snapshot, and nothing is answered until then."""
        self.snapshot = None
        self.topo.nodes = ()

    def at(self, snapshot: Snapshot) -> bool:
        """Whether this is the compiler of `snapshot`, ready to answer."""
        return self.snapshot is not None \
            and self.generation == snapshot.generation \
            and self.ns_epoch == self._resolver_epoch()

    def kept(self) -> None:
        """Asked again at the snapshot pointed at: nothing was read."""
        self.reached = "kept"
        self.walked = self.came = self.gone = 0

    def _reread(self, rows) -> None:
        """Bring the carriers of residents' own terms at node indices
        `rows` up to the two term-carrying lists of the snapshot's nodes:
        add the terms of the pods that came since each list was last
        read, take off those of the ones that went."""
        nodes = self.snapshot.nodes
        walked = came_n = gone_n = 0
        for read, attr, move in (
                (self._read_anti, "pods_with_required_anti_affinity",
                 self._move_anti),
                (self._read_score, "pods_with_affinity", self._move_score)):
            for n in rows:
                now = getattr(nodes[n], attr)
                seen = read[n]
                if not now and not seen:
                    continue
                read[n] = now
                walked += len(now)
                came, gone = came_and_gone(seen, now)
                came_n += len(came)
                gone_n += len(gone)
                for pi in came:
                    move(pi, n, 1)
                for pi in gone:
                    move(pi, n, -1)
        self.walked, self.came, self.gone = walked, came_n, gone_n

    def _move_anti(self, pi: PodInfo, n: int, step: int) -> None:
        ns = pi.namespace
        for term in pi.required_anti_affinity_terms:
            self.resident_anti.move(
                repr((term, ns)), (term, ns), n, 1.0, step)

    def _move_score(self, pi: PodInfo, n: int, step: int) -> None:
        ns, carriers = pi.namespace, self.resident_score
        for sign, preferred in ((1.0, pi.preferred_affinity_terms),
                                (-1.0, pi.preferred_anti_affinity_terms)):
            for t in preferred:
                term = t.get("podAffinityTerm") or {}
                carriers.move(repr((term, ns, False)), (term, ns, False), n,
                              sign * float(t.get("weight", 1)), step)
        for term in pi.required_affinity_terms:
            carriers.move(repr((term, ns, True)), (term, ns, True), n,
                          1.0, step)

    def _drop_counted(self) -> None:
        """Drop every cache derived from pod counts."""
        #: per-pending-pod-signature symmetry-match cache
        self._sym_match_cache: dict[tuple, bool] = {}
        #: per-(term,ns) per-node matching-count cache
        self._count_cache: dict[str, np.ndarray] = {}
        self._mask_cache = {k: v for k, v in self._mask_cache.items()
                            if k.startswith("elig/")}
        #: full-row caches keyed by pod CONTENT signature (namespace,
        #: labels, term list): template-stamped batches share one row —
        #: the per-pod O(N) row assembly was the 5k families' top host
        #: cost. Cached rows are shared and IDENTITY-STABLE per
        #: signature; callers must not mutate them. The backend's
        #: class-dictionary build leans on that stability: its row
        #: interning memoizes by object identity, so a template's
        #: thousand pods hash the row bytes once and land in one device
        #: plane class (ops/backend._prep_chunk).
        self._filter_row_cache: dict[tuple, np.ndarray] = {}
        self._score_row_cache: dict[tuple, np.ndarray] = {}
        self._score_parts_cache: dict[tuple, dict[str, np.ndarray]] = {}

    def advance(self, snapshot: Snapshot, n_pad: int) -> int | None:
        """Move to a later `snapshot` of the same node set by reading
        again the nodes its changed-node log names; returns how many.
        None = the handles do not vouch for it (no handles, node set or a
        node object changed, a namespace relabelled, log too short, node
        count differs) and the caller builds anew."""
        if self.set_epoch < 0 or snapshot.set_epoch != self.set_epoch \
                or snapshot.spec_seq != self.spec_seq \
                or n_pad != self.n_pad \
                or len(snapshot.nodes) != self.n_real \
                or snapshot.generation < self.generation \
                or snapshot.changed_since is None \
                or self.ns_epoch != self._resolver_epoch():
            return None
        changed = snapshot.changed_since(self.generation)
        if changed is None:
            return None
        self._point_at(snapshot)
        self.reached = "delta"
        self._reread(changed)
        if changed:
            self.sigs.recount(snapshot.nodes, changed)
            self._drop_counted()
        return len(changed)

    # -- primitives --------------------------------------------------------

    def counts_for(self, selector: dict | None,
                   namespaces: tuple[str, ...]) -> np.ndarray:
        """(n_pad,) count of resident pods matching selector per node."""
        key = repr((selector, namespaces))
        c = self._count_cache.get(key)
        if c is None:
            c = self.sigs.node_sig_count @ self.sigs.match_vec(
                selector, namespaces)
            self._count_cache[key] = c
        return c

    def _domain_presence(self, counts: np.ndarray,
                         topology_key: str) -> tuple[np.ndarray, np.ndarray]:
        """(per_node_domain_count (n_pad,), has_key (n_pad,))."""
        dom_ids, num = self.topo.domains(topology_key)
        has_key = dom_ids > 0
        d = _seg_sum(np.where(has_key, counts, 0.0), dom_ids, num)
        d[0] = 0.0
        return d[dom_ids], has_key

    # -- per-term masks (cached by term signature) -------------------------

    def supported(self, pod: PodInfo) -> bool:
        """Every term shape compiles (namespaceSelector included) —
        retained as a seam for future exotic term shapes."""
        return True

    def anti_term_mask(self, term: dict, owner_ns: str) -> np.ndarray:
        key = "anti/" + repr((term, owner_ns))
        m = self._mask_cache.get(key)
        if m is None:
            counts = self.counts_for(term.get("labelSelector"),
                                     _term_ns(term, owner_ns, self.ns_resolver))
            per_node, has_key = self._domain_presence(
                counts, term.get("topologyKey", ""))
            m = ~has_key | (per_node == 0)
            self._mask_cache[key] = m
        return m

    def affinity_term_presence(self, term: dict,
                               owner_ns: str) -> tuple[np.ndarray, np.ndarray, float]:
        """(per_node matching count, has_key, total matches anywhere)."""
        key = "aff/" + repr((term, owner_ns))
        got = self._mask_cache.get(key)
        if got is None:
            counts = self.counts_for(term.get("labelSelector"),
                                     _term_ns(term, owner_ns, self.ns_resolver))
            tk = term.get("topologyKey", "")
            per_node, has_key = self._domain_presence(counts, tk)
            # `total` drives the first-pod-in-group escape: the host plugin
            # only counts matches on nodes that HAVE the topology key
            # (pre_filter skips tv-None nodes), so mask accordingly.
            total = float(np.sum(np.where(
                has_key[: self.n_real], counts[: self.n_real], 0.0)))
            got = (per_node, has_key, total)
            self._mask_cache[key] = got
        return got

    def symmetry_mask(self, pod: PodInfo) -> np.ndarray:
        """Nodes forbidden to `pod` by resident pods' required anti-affinity
        (the both-ways check in filtering.go)."""
        mask = np.ones((self.n_pad,), dtype=np.bool_)
        if not self.resident_anti:
            return mask
        from kubernetes_tpu.api.labels import from_label_selector
        pod_sig = (pod.namespace, tuple(sorted(pod.labels.items())))
        for key, (carriers, term, owner_ns) in self.resident_anti.items():
            mk = (key, pod_sig)
            hit = self._sym_match_cache.get(mk)
            if hit is None:
                nses = _term_ns(term, owner_ns, self.ns_resolver)
                hit = ns_contains(nses, pod.namespace) and \
                    from_label_selector(
                        term.get("labelSelector")).matches(pod.labels)
                self._sym_match_cache[mk] = hit
            if not hit:
                continue
            skey = "sym/" + key
            m = self._mask_cache.get(skey)
            if m is None:
                per_node, has_key = self._domain_presence(
                    carriers, term.get("topologyKey", ""))
                m = ~has_key | (per_node == 0)
                self._mask_cache[skey] = m
            mask &= m
        return mask

    # -- the batch entry ----------------------------------------------------

    def filter_row(self, pod: PodInfo) -> np.ndarray:
        """(n_pad,) bool feasibility row for one pending pod — exact
        InterPodAffinity.Filter semantics over the snapshot. Cached by
        pod CONTENT signature (template batches share one row); the
        returned array is shared — do not mutate."""
        ck = (pod.namespace, tuple(sorted(pod.labels.items())),
              repr(pod.required_affinity_terms),
              repr(pod.required_anti_affinity_terms))
        cached = self._filter_row_cache.get(ck)
        if cached is not None:
            return cached
        row = self.symmetry_mask(pod).copy()
        for term in pod.required_anti_affinity_terms:
            row &= self.anti_term_mask(term, pod.namespace)
        if pod.required_affinity_terms:
            # first-pod-in-group rule: if NO term matches anything anywhere
            # and the pod matches its own terms, terms don't reject (nodes
            # still need the topology keys).
            presences = [
                self.affinity_term_presence(t, pod.namespace)
                for t in pod.required_affinity_terms]
            total_any = sum(p[2] for p in presences)
            if total_any == 0 and self._self_matches(pod):
                for _, has_key, _ in presences:
                    row &= has_key
            else:
                for per_node, has_key, _ in presences:
                    row &= has_key & (per_node > 0)
        row[self.n_real:] = False
        self._filter_row_cache[ck] = row
        return row

    def score_supported(self, pod: PodInfo) -> bool:
        """Preferred terms compile like required ones (namespaceSelector
        included) — retained as a seam, always True."""
        return True

    def _masked_presence(self, counts: np.ndarray, topology_key: str,
                         feasible: np.ndarray
                         ) -> tuple[np.ndarray, np.ndarray]:
        """_domain_presence restricted to feasible nodes — the host
        pre_score iterates only the FILTERED node list, so residents on
        infeasible nodes contribute nothing (per-pod, so uncached)."""
        dom_ids, num = self.topo.domains(topology_key)
        has_key = dom_ids > 0
        d = _seg_sum(np.where(has_key & feasible, counts, 0.0),
                     dom_ids, num)
        d[0] = 0.0
        return d[dom_ids], has_key

    def _selects(self, term: dict, owner_ns: str, pod: PodInfo) -> bool:
        """Does `term`, owned by a pod in `owner_ns`, select `pod`?
        Cached by term and the pod's namespace and labels."""
        mk = ("sel", repr(term), owner_ns, pod.namespace,
              tuple(sorted(pod.labels.items())))
        hit = self._sym_match_cache.get(mk)
        if hit is None:
            hit = self._sym_match_cache[mk] = ns_contains(
                _term_ns(term, owner_ns, self.ns_resolver),
                pod.namespace) and from_label_selector(
                term.get("labelSelector")).matches(pod.labels)
        return hit

    def score_parts(self, pod: PodInfo,
                    hard_weight: float) -> dict[str, np.ndarray]:
        """The pod's raw InterPodAffinity weight per node, before any
        node is left out, by topology key: {key: (n_pad,) float32}. A
        node's entry sums what the residents ON it add — the pod's
        preferred (anti-)terms weigh the residents they select, and the
        residents' preferred terms and required affinity terms
        (× `hard_weight`) that select the pod weigh back (scoring.go's
        two loops). `score_row` folds these into domains over the
        feasible nodes; a chunk whose own placements move the pod's
        weight carries them on the device as they are (ops/solver.py,
        `ipa=`). Cached by pod content signature. Shared arrays — do not
        mutate."""
        ck = (pod.namespace, tuple(sorted(pod.labels.items())),
              repr(pod.preferred_affinity_terms),
              repr(pod.preferred_anti_affinity_terms), hard_weight)
        parts = self._score_parts_cache.get(ck)
        if parts is not None:
            return parts
        parts = {}

        def add(key: str, vec: np.ndarray, w: float) -> None:
            got = parts.get(key)
            if got is None:
                got = parts[key] = np.zeros((self.n_pad,), dtype=np.float32)
            got += w * vec

        for sign, preferred in ((1.0, pod.preferred_affinity_terms),
                                (-1.0, pod.preferred_anti_affinity_terms)):
            for term in preferred:
                t = term.get("podAffinityTerm") or {}
                add(t.get("topologyKey", ""), self.counts_for(
                    t.get("labelSelector"),
                    _term_ns(t, pod.namespace, self.ns_resolver)),
                    sign * float(term.get("weight", 1)))
        for carriers, term, owner_ns, is_hard in \
                self.resident_score.values():
            if self._selects(term, owner_ns, pod):
                add(term.get("topologyKey", ""), carriers,
                    hard_weight if is_hard else 1.0)
        for vec in parts.values():
            vec[self.n_real:] = 0.0
        self._score_parts_cache[ck] = parts
        return parts

    def score_delta(self, pod: PodInfo, other: PodInfo,
                    hard_weight: float) -> dict[str, float]:
        """What one more pod like `other` adds to `pod`'s raw weight on
        the node it lands on, by topology key (`score_parts`' terms: the
        pod's terms that select it, and its terms that select the pod).
        Keys whose weights cancel are left out."""
        out: dict[str, float] = {}
        for owner, target, hard in ((pod, other, False),
                                    (other, pod, True)):
            for sign, preferred in (
                    (1.0, owner.preferred_affinity_terms),
                    (-1.0, owner.preferred_anti_affinity_terms)):
                for term in preferred:
                    t = term.get("podAffinityTerm") or {}
                    if self._selects(t, owner.namespace, target):
                        key = t.get("topologyKey", "")
                        out[key] = out.get(key, 0.0) \
                            + sign * float(term.get("weight", 1))
            if hard:
                for t in owner.required_affinity_terms:
                    if self._selects(t, owner.namespace, target):
                        key = t.get("topologyKey", "")
                        out[key] = out.get(key, 0.0) + hard_weight
        return {k: w for k, w in out.items() if w}

    def score_row(self, pod: PodInfo, hard_weight: float,
                  feasible: np.ndarray) -> np.ndarray:
        """(n_pad,) raw InterPodAffinity score — exactly pre_score's
        domain-weight accumulation (scoring.go) over the pod's FEASIBLE
        nodes: `score_parts` summed by domain over the feasible nodes of
        each key. Cached by (pod content signature, feasible-mask
        bytes): template batches share one row per distinct feasibility
        class. Shared array — do not mutate."""
        ck = (pod.namespace, tuple(sorted(pod.labels.items())),
              repr(pod.preferred_affinity_terms),
              repr(pod.preferred_anti_affinity_terms),
              hard_weight, feasible.tobytes())
        cached = self._score_row_cache.get(ck)
        if cached is not None:
            return cached
        row = np.zeros((self.n_pad,), dtype=np.float32)
        for key, vec in self.score_parts(pod, hard_weight).items():
            per_node, has_key = self._masked_presence(vec, key, feasible)
            row += np.where(has_key, per_node, 0.0)
        row[self.n_real:] = 0.0
        self._score_row_cache[ck] = row
        return row

    def _self_matches(self, pod: PodInfo) -> bool:
        from kubernetes_tpu.api.labels import from_label_selector
        for t in pod.required_affinity_terms:
            if not ns_contains(
                    _term_ns(t, pod.namespace, self.ns_resolver),
                    pod.namespace):
                return False
            if not from_label_selector(t.get("labelSelector")).matches(pod.labels):
                return False
        return True

    # -- PodTopologySpread (same primitives, skew semantics) ---------------

    def eligibility_row(self, pod: PodInfo) -> np.ndarray:
        """(n_pad,) nodes eligible for domain counting under this pod's
        nodeSelector/affinity/tolerations (podtopologyspread._node_eligible),
        cached by the pod's eligibility signature."""
        key = "elig/" + repr((pod.node_selector,
                              pod.affinity.get("nodeAffinity"),
                              pod.tolerations))
        row = self._mask_cache.get(key)
        if row is None:
            from kubernetes_tpu.scheduler.plugins.podtopologyspread import (
                _node_eligible,
            )
            row = np.zeros((self.n_pad,), dtype=np.bool_)
            for n, ni in enumerate(self.snapshot.nodes):
                row[n] = _node_eligible(pod, ni)
            self._mask_cache[key] = row
        return row

    def spread_constraint_ns(self, constraint: dict,
                             pod_ns: str) -> tuple[str, ...]:
        """A spread constraint's effective namespace set (plain
        constraints count within the pod's own namespace;
        namespaceSelector resolves like an affinity term's)."""
        return _term_ns(constraint, pod_ns, self.ns_resolver)

    def _spread_domain_counts(self, pod: PodInfo, constraint: dict):
        """Per-constraint: (per_node_count, has_key, eligible, min_count).

        Host semantics (_build_state): only eligible nodes' pods count and
        only eligible domains exist; min is over eligible domains, floored
        to 0 when fewer eligible domains exist than minDomains."""
        key = "spread/" + repr((constraint, pod.namespace,
                                pod.node_selector,
                                pod.affinity.get("nodeAffinity"),
                                pod.tolerations))
        got = self._mask_cache.get(key)
        if got is None:
            sel = constraint.get("labelSelector")
            counts = self.counts_for(
                sel, self.spread_constraint_ns(constraint, pod.namespace))
            elig = self.eligibility_row(pod)
            tk = constraint["topologyKey"]
            dom_ids, num = self.topo.domains(tk)
            has_key = dom_ids > 0
            active = has_key & elig
            d = _seg_sum(np.where(active, counts, 0.0), dom_ids, num)
            # Domains with at least one eligible node "exist" (count ≥ 0);
            # others are fresh (None in the host dict → constraint passes).
            exists = _seg_sum(active.astype(np.float32), dom_ids, num) > 0
            exists[0] = False
            n_existing = int(exists.sum())
            md = int(constraint.get("minDomains") or 0)
            if md and n_existing < md:
                min_count = 0.0
            else:
                min_count = float(d[exists].min()) if n_existing else 0.0
            got = (d[dom_ids], has_key, exists[dom_ids], min_count)
            self._mask_cache[key] = got
        return got

    def spread_filter_row(self, pod: PodInfo,
                          constraints: list[dict]) -> np.ndarray:
        """(n_pad,) DoNotSchedule skew feasibility
        (podtopologyspread.filter)."""
        row = np.ones((self.n_pad,), dtype=np.bool_)
        for c in constraints:
            per_node, has_key, exists, min_count = \
                self._spread_domain_counts(pod, c)
            max_skew = c.get("maxSkew", 1)
            # selfMatchNum (filtering.go): count the incoming pod only if
            # the constraint's selector + namespace set match the pod.
            self_match = 1 if ns_contains(
                self.spread_constraint_ns(c, pod.namespace),
                pod.namespace) and from_label_selector(
                c.get("labelSelector")).matches(pod.labels) else 0
            ok = (~exists) | (per_node + self_match - min_count <= max_skew)
            row &= has_key & ok
        row[self.n_real:] = False
        return row

    def spread_raw_scores(self, pod: PodInfo,
                          constraints: list[dict]) -> np.ndarray:
        """(n_pad,) raw ScheduleAnyway score: Σ matching-pod count in the
        node's domains (podtopologyspread.score; NormalizeScore inverts)."""
        raw = np.zeros((self.n_pad,), dtype=np.float32)
        for c in constraints:
            per_node, has_key, _, _ = self._spread_domain_counts(pod, c)
            raw += np.where(has_key, per_node, 0.0)
        return raw
