"""On-device batched pod→node assignment.

This replaces the reference's one-pod-at-a-time `schedulePod` →
`findNodesThatFitPod` → `prioritizeNodes` → `selectHost` chain
(pkg/scheduler/schedule_one.go) with a single XLA program over the whole
pending batch. Intra-batch resource contention — the correctness hazard
SURVEY §3.1 flags for batched popping — is resolved *inside* the kernel:
the scan thread capacity through pod steps, so a batch's assignments are
exactly what P sequential host cycles would produce (same priority order,
same capacity accounting), minus the per-cycle Python/framework overhead.

Two solvers:

- `greedy_assign` — lax.scan in queue (priority) order. Each step masks
  by remaining capacity, picks argmax(score), debits the chosen node.
  Deterministic (ties → lowest node index; the host path's seeded reservoir
  tiebreak is equivalent up to tie choice). This is the oracle-equivalent
  default. The serial scan handles one pod per step; the `_wave` variants
  below handle W pods per step with the same assignments bit-for-bit.
- `multistart_greedy_assign` — the contention solver: the SAME scan under
  K pod orders in parallel (vmap over permutations), gang all-or-nothing
  masking, keep the order that places the most pods; identity order wins
  ties so uncontended batches equal the oracle bit-for-bit.

Speculative wavefront scans (`*_wave`): the serial scan's length P is the
wall at scale — every step is a chain of tiny ops dispatched in sequence.
The wavefront form evaluates W pods per scan step against the SAME carry
state, commits the wave's prefix-distinct argmax choices speculatively,
and falls back to an in-step serial replay (`lax.fori_loop` over the
wave) exactly when a pairwise conflict check cannot prove the speculation
serial-equivalent — so assignments are **bit-identical at every W** (the
same contract the shortlist and class-plane scans hold) while the scan
length drops P → P/W in the low-conflict regime. See
`greedy_assign_rescoring_wave` for the speculation/replay contract.

Both are shape-static, jit-compiled once per (P, N, R) signature, and emit
`(P,) int32` node indices with -1 = unschedulable-this-cycle. P is the
PADDED chunk width; how many of its steps run follows the chunk's real pod
count `p_real`, a traced scalar every chunk scan takes (`_scan_real`): a
trickled chunk of two pods runs one wave step, not the 32 of 1,024 pods.

Class-dictionary planes: every scan reads `mask`/`static_scores` as
CLOSED-OVER planes addressed per step through `rows` — a (P,) row index
mapping each pod to its plane row. The backend ships (C, N) planes over
pod EQUIVALENCE CLASSES (pods sharing request/toleration/host-row/score
signatures — template batches have a handful) with `rows = class index
per pod`, so no (P, N) plane exists on host or device; the legacy
per-pod form is the degenerate `rows = arange(P)` (C == P), which is
also the KTPU_CLASS_PAD=0 kill-switch shape. Per-pod residuals that
would otherwise split a class — single-allowed-column host rows
(NodeName, DRA allocated-claim pinning) — ride the sparse exception
vector `exc`: (P,) int32, -1 = none, else the ONE global column the pod
is additionally restricted to (intersected with its class row).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -jnp.inf


def _scan_real(step, carry0, xs, n_steps=None, y_shape=()):
    """`lax.scan(step, carry0, xs)` over the first `n_steps` steps only,
    for a step whose stacked output is one int32 array of node indices
    (`y_shape` a step: () per pod, (W,) per wave).

    Every chunk scan below runs over a chunk padded to a fixed width, and
    a padded step places nothing and moves no state (its mask row is
    all-False) — it only costs a full-width pass. `n_steps` (traced int32 scalar: the chunk's real
    steps; None = all of them) bounds the loop instead, so the trip count
    follows the chunk's real pods while every shape stays the padded one:
    the stacked outputs are preallocated at full length holding -1, which
    is what a padded step writes.

    The predicate reads the step index and `n_steps` alone. Callers under
    the multistart vmap close `n_steps` over from OUTSIDE the vmapped
    function (every order keeps padding in place, so all orders share one
    real prefix): the predicate then stays unbatched and the loop a loop.
    A batched predicate would lower to a select that runs every step.
    """
    length = jax.tree.leaves(xs)[0].shape[0]
    if n_steps is None:
        n_steps = length
    steps = jnp.arange(length, dtype=jnp.int32).reshape(
        (length,) + (1,) * len(y_shape))
    # Tie the initial carry to the inputs (a select between a value and
    # itself: nothing at run time). Under the multistart vmap the carry
    # is then batched from the start, as it is after the first step
    # anyway, and JAX's batching rule for `while` finds its fixpoint in
    # one pass over the body in place of two — the W-unrolled wave step
    # is thousands of equations, and the extra pass read as +10–17% of a
    # cell's set-up on the chip's host (PERF.md, PR 31).
    lead = jax.tree.leaves(xs)[0]
    never = lead[(0,) * lead.ndim] != lead[(0,) * lead.ndim]
    carry0, ys0 = jax.tree.map(
        lambda c: jnp.where(never, c, c),
        (carry0, jnp.full((length,) + y_shape, -1, jnp.int32)))

    def body(state):
        i, carry, ys = state
        carry, y = step(carry, jax.tree.map(
            lambda x: lax.dynamic_index_in_dim(
                x, i, 0, keepdims=False, allow_negative_indices=False), xs))
        # Row i of the few-KB output by a select, not by a
        # dynamic-update-slice: under the multistart vmap that would
        # batch into a scatter — a bounds check, a read and a select
        # before the write, three more kernels a step on the TPU.
        return i + 1, carry, jnp.where(steps == i, y, ys)

    _, carry, ys = lax.while_loop(
        lambda state: state[0] < n_steps, body,
        (jnp.int32(0), carry0, ys0))
    return carry, ys


@jax.jit
def greedy_assign(req_q, free_q, free_pods, mask, scores):
    """Sequential-equivalent batched greedy.

    req_q: (P,R) int32 quantized requests (row order = scheduling order)
    free_q: (N,R) int32 remaining capacity (alloc_q - used_q)
    free_pods: (N,) int32 remaining pod slots
    mask: (P,N) bool non-capacity feasibility (plugins other than resources)
    scores: (P,N) float32 combined weighted scores
    → (P,) int32 node index or -1
    """
    n = free_q.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)

    def step(carry, inp):
        free_q, free_pods = carry
        req, m, sc = inp
        fits = m & jnp.all(req[None, :] <= free_q, axis=1) & (free_pods >= 1)
        any_fit = jnp.any(fits)
        masked = jnp.where(fits, sc, NEG_INF)
        idx = jnp.argmax(masked).astype(jnp.int32)
        idx = jnp.where(any_fit, idx, jnp.int32(-1))
        hit = iota == idx
        free_q = free_q - jnp.where(hit[:, None], req[None, :], 0)
        free_pods = free_pods - hit.astype(jnp.int32)
        return (free_q, free_pods), idx

    (_, _), assign = lax.scan(step, (free_q, free_pods), (req_q, mask, scores))
    return assign


def _ipa_score(ipa, placed, a, fits):
    """InterPodAffinity's weighted score of pod row `a` (-1: none) on every
    node, normalised over this step's feasible nodes `fits`, from counts
    that move with the chunk's own placements — what
    `InterPodAffinity.pre_score` + `normalize_scores` give the host
    scheduler one pod at a time.

    `ipa` = (base, delta, keyed, dom, dom_key, w100, ...):
    base (A, K, N) the raw weight each carried pod row gets per node and
    topology key from the residents at the snapshot (before any node is
    left out: AffinityCompiler.score_parts); delta (A, T, K) what one
    placed pod of touching row t adds to it on its node; keyed (K, N)
    1.0 where key k names every keyed node alone (hostname: a node is
    its own domain); dom (N, D) node -> domain one-hot over the other
    keys' domains, owned by key through dom_key (D, K); w100 the
    profile's weight x 100. `placed` (T, N) counts this assign()'s
    placements of each touching row per node.

    A domain sums what its FEASIBLE nodes hold (pre_score walks the
    filtered nodes); max == min adds nothing."""
    base, delta, keyed, dom, dom_key, w100 = ipa[:6]
    sa = jnp.maximum(a, 0)
    u = base[sa] + delta[sa].T @ placed                         # (K, N)
    raw = jnp.sum(keyed * u, axis=0)
    if dom.shape[1]:
        held = dom_key @ (u * fits.astype(jnp.float32))         # (D, N)
        raw = raw + dom @ jnp.sum(dom.T * held, axis=1)
    hi = jnp.max(jnp.where(fits, raw, NEG_INF))
    lo = jnp.min(jnp.where(fits, raw, jnp.inf))
    span = hi - lo
    live = (a >= 0) & (span > 0)
    return jnp.where(live, w100 * (raw - lo) / jnp.where(live, span, 1.0),
                     0.0)


def _ipa_place(placed, t, idx):
    """One more pod of touching row `t` (-1: none) on node `idx`."""
    ok = (t >= 0) & (idx >= 0)
    return placed.at[jnp.maximum(t, 0), jnp.maximum(idx, 0)].add(
        jnp.where(ok, 1.0, 0.0))


@partial(jax.jit, static_argnames=("strategy",))
def greedy_assign_rescoring(req_q, req_nz_q, free_q, free_pods, used_nz_q,
                            alloc_q, mask, static_scores, fit_col_w,
                            bal_col_mask, shape_u, shape_s, w_fit, w_bal,
                            strategy: str, rows=None, exc=None, p_real=None,
                            ipa=None):
    """Sequential-equivalent greedy with **live re-scoring**.

    The capacity-dependent score plugins (NodeResourcesFit strategies,
    BalancedAllocation) are recomputed inside each scan step from the
    *current* used-resources state — exactly what P sequential host cycles
    see (each cycle re-snapshots after the previous assume). Without this,
    a batch of identical pods all score the batch-start state and pile onto
    one node, wrecking the balance/fragmentation the scorers exist for.

    Capacity-independent score components (taints, host rows, weights
    already applied) arrive pre-summed in `static_scores` — (C, N) class
    planes addressed through `rows` (see module docstring); with
    rows=None the planes are per-pod (C == P, row = pod). `exc` is the
    optional (P,) single-allowed-column restriction (-1 = none).
    `p_real` (traced int32, None = P) is the chunk's real pod count: the
    scan stops there (`_scan_real`), as in every chunk scan below.

    `ipa` (see `_ipa_score`; its last three entries are `placed` (T, N)
    and the per-pod (P,) carried row and touching row, -1 = none) adds
    the InterPodAffinity score of pods whose score the chunk's own
    placements move, and returns (assign, placed') so that the counts
    chain across chunks like the used-state.
    """
    from kubernetes_tpu.ops import kernels  # local to avoid import cycle

    n = free_q.shape[0]
    p = req_q.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    if rows is None:
        rows = jnp.arange(p, dtype=jnp.int32)

    def step(carry, inp):
        if ipa is not None:
            carry, placed = carry[:3], carry[3]
            inp, (ia, it) = inp[:-2], inp[-2:]
        free_q, free_pods, used_nz = carry
        if exc is None:
            req, req_nz, row = inp
        else:
            req, req_nz, row, e = inp
        m = mask[row]
        if exc is not None:
            m = m & ((e < 0) | (iota == e))
        fits = m & jnp.all(req[None, :] <= free_q, axis=1) & (free_pods >= 1)
        any_fit = jnp.any(fits)
        sc = static_scores[row]
        sc = sc + w_fit * kernels.fit_score(
            alloc_q, used_nz, req_nz[None, :], fit_col_w, strategy,
            shape_u, shape_s)[0]
        sc = sc + w_bal * kernels.balanced_allocation_score(
            alloc_q, used_nz, req_nz[None, :], bal_col_mask)[0]
        if ipa is not None:
            sc = sc + _ipa_score(ipa, placed, ia, fits)
        masked = jnp.where(fits, sc, NEG_INF)
        idx = jnp.argmax(masked).astype(jnp.int32)
        idx = jnp.where(any_fit, idx, jnp.int32(-1))
        hit = iota == idx
        free_q = free_q - jnp.where(hit[:, None], req[None, :], 0)
        free_pods = free_pods - hit.astype(jnp.int32)
        used_nz = used_nz + jnp.where(hit[:, None], req_nz[None, :], 0)
        if ipa is not None:
            return (free_q, free_pods, used_nz,
                    _ipa_place(placed, it, idx)), idx
        return (free_q, free_pods, used_nz), idx

    xs = (req_q, req_nz_q, rows) if exc is None \
        else (req_q, req_nz_q, rows, exc)
    carry0 = (free_q, free_pods, used_nz_q)
    if ipa is not None:
        xs = xs + tuple(ipa[-2:])
        carry0 = carry0 + (ipa[-3],)
    carry, assign = _scan_real(step, carry0, xs, p_real)
    if ipa is not None:
        return assign, carry[3]
    return assign


@partial(jax.jit, static_argnames=("strategy",))
def greedy_assign_rescoring_spread(req_q, req_nz_q, free_q, free_pods,
                                   used_nz_q, alloc_q, mask, static_scores,
                                   fit_col_w, bal_col_mask, shape_u, shape_s,
                                   w_fit, w_bal, strategy: str,
                                   dom_onehot, cid_onehot, dom_counts,
                                   max_skew, min_ok, has_key_nc,
                                   applies, contributes, rows=None,
                                   exc=None, p_real=None, ipa=None):
    """greedy_assign_rescoring + PodTopologySpread hard constraints INSIDE
    the scan (sequential-equivalent, like capacity).

    The batch-then-verify split is pathological for tight `maxSkew`: the
    solver's batch-start masks let every pod into one domain, the host
    verify rejects all but ~(domains × maxSkew) per batch, and throughput
    collapses to a requeue loop. The domain counts ride the scan carry
    instead — and the constraint set is the UNION across every spread
    template in the batch, so heterogeneous batches (several templates,
    minDomains/namespaceSelector constraints, restricted node
    eligibility, non-self-matching selectors, plus non-spread pods
    matching some template's selector) ALL stay on device:

    dom_onehot: (N, D) float32 — node → domain one-hot over the union of
        ALL constraints' eligible domains (the template's node-eligibility
        mask is folded in per constraint column: ineligible nodes belong
        to no domain and neither count nor gate).
    cid_onehot: (D, C) float32 — domain → owning constraint.
    dom_counts: (D,) float32 — batch-start matching-pod count per domain
        (eligible nodes only, the owning constraint's namespace set).
    max_skew:   (C,) float32 per constraint.
    min_ok:     (C,) float32 — 0.0 when the constraint has fewer eligible
        domains than its minDomains (global minimum is then treated as 0,
        the k8s MinDomainsInPodTopologySpread rule), else 1.0.
    has_key_nc: (N, C) float32 — node HAS the constraint's topology key
        (regardless of eligibility). Keyless nodes reject
        (DoNotSchedule); keyed nodes outside every eligible domain pass
        as "fresh" (the host plugin's count-is-None continue). A keyed-
        but-INELIGIBLE node whose domain value does exist eligible
        elsewhere also fresh-passes here — sound because eligibility is
        the pod's own nodeSelector/affinity/tolerations, so the static
        and taint masks already reject that node for this pod.
    applies:     (P, C) float32 — constraint c GATES pod p's placement
        (p carries it in its own template).
    contributes: (P, C) float32 — pod p COUNTS toward constraint c when
        placed (namespace + selector match) — computed for every pod in
        the chunk, spread-constrained or not. Doubles as the per-pod
        selfMatch term of the skew check (filtering.go selfMatchNum).

    `rows`/`exc` are the class-plane indirection of the module docstring
    (rows=None ⇒ per-pod planes). applies/contributes stay per-pod.

    Returns (assign, dom_counts') so the caller can chain counts across
    chunks on device, exactly like the packed used-state; with `ipa`
    (greedy_assign_rescoring's) (assign, dom_counts', placed').
    """
    from kubernetes_tpu.ops import kernels  # local to avoid import cycle

    n = free_q.shape[0]
    p = req_q.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    big = jnp.float32(1e30)
    if rows is None:
        rows = jnp.arange(p, dtype=jnp.int32)
    # Static per-constraint node→eligible-domain membership: nodes outside
    # it (but keyed) take the fresh-domain pass.
    in_dom_nc = (dom_onehot @ cid_onehot) > 0                          # (N,C)
    gate_nc = has_key_nc > 0

    def step(carry, inp):
        if ipa is not None:
            carry, placed = carry[:4], carry[4]
            inp, (ia, it) = inp[:-2], inp[-2:]
        free_q, free_pods, used_nz, dcounts = carry
        if exc is None:
            req, req_nz, row, app, contrib = inp
        else:
            req, req_nz, row, app, contrib, e = inp
        m = mask[row]
        if exc is not None:
            m = m & ((e < 0) | (iota == e))
        sc_static = static_scores[row]
        # min count over each constraint's domains (empty domains included),
        # floored to 0 under a minDomains deficit.
        min_c = jnp.min(
            jnp.where(cid_onehot > 0, dcounts[:, None], big), axis=0)  # (C,)
        min_c = min_c * min_ok
        self_d = cid_onehot @ contrib                                  # (D,)
        allowed_d = (dcounts + self_d - cid_onehot @ min_c) \
            <= (cid_onehot @ max_skew)                                 # (D,)
        in_allowed = (dom_onehot @ (allowed_d[:, None] * cid_onehot)) > 0
        # Every constraint THE POD CARRIES: the node must have the
        # topology key (DoNotSchedule rejects keyless nodes), and if it
        # belongs to one of the constraint's eligible domains, that
        # domain's skew must allow this pod's selfMatch increment; keyed
        # nodes outside every eligible domain are fresh and pass.
        node_c_ok = gate_nc & (in_allowed | jnp.logical_not(in_dom_nc))
        spread_ok = jnp.all(node_c_ok | (app[None, :] == 0), axis=1)
        fits = m & jnp.all(req[None, :] <= free_q, axis=1) & (free_pods >= 1)
        fits = fits & spread_ok
        any_fit = jnp.any(fits)
        sc = sc_static
        sc = sc + w_fit * kernels.fit_score(
            alloc_q, used_nz, req_nz[None, :], fit_col_w, strategy,
            shape_u, shape_s)[0]
        sc = sc + w_bal * kernels.balanced_allocation_score(
            alloc_q, used_nz, req_nz[None, :], bal_col_mask)[0]
        if ipa is not None:
            sc = sc + _ipa_score(ipa, placed, ia, fits)
        masked = jnp.where(fits, sc, NEG_INF)
        idx = jnp.argmax(masked).astype(jnp.int32)
        idx = jnp.where(any_fit, idx, jnp.int32(-1))
        hit = iota == idx
        free_q = free_q - jnp.where(hit[:, None], req[None, :], 0)
        free_pods = free_pods - hit.astype(jnp.int32)
        used_nz = used_nz + jnp.where(hit[:, None], req_nz[None, :], 0)
        # The placed pod counts in the domains of constraints it MATCHES
        # (cid @ contrib masks the chosen node's domain one-hot per
        # constraint ownership).
        dcounts = dcounts + jnp.where(
            any_fit,
            (hit.astype(jnp.float32) @ dom_onehot) * (cid_onehot @ contrib),
            0.0)
        if ipa is not None:
            return (free_q, free_pods, used_nz, dcounts,
                    _ipa_place(placed, it, idx)), idx
        return (free_q, free_pods, used_nz, dcounts), idx

    xs = (req_q, req_nz_q, rows, applies, contributes) if exc is None \
        else (req_q, req_nz_q, rows, applies, contributes, exc)
    carry0 = (free_q, free_pods, used_nz_q, dom_counts)
    if ipa is not None:
        xs = xs + tuple(ipa[-2:])
        carry0 = carry0 + (ipa[-3],)
    carry, assign = _scan_real(step, carry0, xs, p_real)
    if ipa is not None:
        return assign, carry[3], carry[4]
    return assign, carry[3]


@partial(jax.jit, static_argnames=("strategy",))
def multistart_greedy_assign(req_q, req_nz_q, free_q, free_pods, used_nz_q,
                             alloc_q, mask, static_scores, fit_col_w,
                             bal_col_mask, shape_u, shape_s, w_fit, w_bal,
                             strategy: str, perms, gang_onehot,
                             gang_required, rows=None, exc=None,
                             p_real=None):
    """K permuted greedy scans in parallel + gang all-or-nothing.

    Sequential greedy in queue order is the oracle, but it strands capacity
    under contention (e.g. nodes of 4 CPU with queue [3,3,2,2,2]: the two
    3s split the nodes and every 2 is stranded; order [2,2,2,...] places
    three pods). The whole batch is known up front, so run the SAME
    sequential-equivalent scan under K pod orders at once — vmap over
    permutations, each scan threading its own capacity — and keep the
    order that places the most pods. perms[0] must be the identity and
    wins ties, so uncontended batches stay bit-identical to the oracle.

    Gangs (Coscheduling all-or-nothing, SURVEY §2.8's EP-analog row):
    gang_onehot (P, G) marks members, gang_required (G,) the minMember
    floor; a scan's partial gang placements are dropped before counting,
    making under-quota gangs atomic failures inside the solver rather
    than Permit-barrier churn.

    perms: (K, P) int32 permutations of the pod axis.
    Returns (P,) int32 chosen assignment (-1 = unassigned).
    """
    return _multistart_body(
        req_q, req_nz_q, free_q, free_pods, used_nz_q, alloc_q, mask,
        static_scores, fit_col_w, bal_col_mask, shape_u, shape_s, w_fit,
        w_bal, strategy, perms, gang_onehot, gang_required, rows, exc,
        p_real)


def _multistart_body(req_q, req_nz_q, free_q, free_pods, used_nz_q, alloc_q,
                     mask, static_scores, fit_col_w, bal_col_mask, shape_u,
                     shape_s, w_fit, w_bal, strategy, perms, gang_onehot,
                     gang_required, rows=None, exc=None, p_real=None):
    """Traceable multistart core — also the shortlist path's whole-chunk
    fallback branch (see multistart_greedy_assign_shortlist).

    Only the small per-pod vectors permute; the (C, N) planes stay
    closed-over and each order addresses them through `rows[perm]` —
    permuting the planes themselves would materialize one (P, N) copy
    per order, exactly what the class-dictionary format removes.
    `p_real` is closed over from outside the vmapped `one`: every order
    keeps padding in place, so all scans stop at one unbatched count."""
    P = req_q.shape[0]
    arange_p = jnp.arange(P, dtype=jnp.int32)
    if rows is None:
        rows = arange_p

    def one(perm):
        a = greedy_assign_rescoring(
            req_q[perm], req_nz_q[perm], free_q, free_pods, used_nz_q,
            alloc_q, mask, static_scores, fit_col_w,
            bal_col_mask, shape_u, shape_s, w_fit, w_bal, strategy,
            rows=rows[perm], exc=None if exc is None else exc[perm],
            p_real=p_real)
        inv = jnp.zeros_like(perm).at[perm].set(arange_p)
        return a[inv]

    assigns = jax.vmap(one)(perms)                         # (K, P)
    return _select_best(assigns, req_q, gang_onehot, gang_required)


def _select_best(assigns, req_q, gang_onehot, gang_required):
    """Gang-filter K candidate assignments and keep the best order."""
    eff = jax.vmap(
        lambda a: gang_filter(a, gang_onehot, gang_required))(assigns)
    placed = eff >= 0
    n_placed = jnp.sum(placed, axis=1).astype(jnp.float32)
    # Tie-break on total placed request volume: at equal pod count the
    # order that consumes MORE capacity strands less (strictly better
    # fragmentation). Full ties → lowest k (identity = oracle).
    sizes = jnp.sum(req_q, axis=1).astype(jnp.float32)     # (P,)
    vol = jnp.sum(jnp.where(placed, sizes[None, :], 0.0), axis=1)
    vol_norm = vol / jnp.maximum(jnp.max(vol), 1.0)
    best = jnp.argmax(n_placed + 0.5 * vol_norm)
    return eff[best]


def gang_filter(assign, gang_onehot, gang_required):
    """Drop placements of gangs below their required member count."""
    placed = (assign >= 0).astype(jnp.float32)
    counts = placed @ gang_onehot                          # (G,)
    gang_ok = (counts >= gang_required).astype(jnp.float32)
    pod_in_gang = jnp.sum(gang_onehot, axis=1) > 0
    pod_ok = (gang_onehot @ gang_ok) > 0
    keep = (assign >= 0) & (pod_ok | ~pod_in_gang)
    return jnp.where(keep, assign, -1)


# ---------------------------------------------------------------------------
# Shortlist-pruned solve: per-pod top-K candidate columns with an exactness
# fallback — the O(P·K + fallbacks·N) form of the sequential-equivalent scan
# for large N (the 50k-node preset is bound by the N-wide inner reduce).
# ---------------------------------------------------------------------------

def shortlist_prefilter(feas0, sc0, k: int):
    """Per-row top-K candidate columns + the exactness threshold.

    feas0: (S,N) bool chunk-start feasibility (static mask ∧ capacity fit;
        within a chunk capacity only DECREASES, so a chunk-start-infeasible
        node can never become the winner — spread gating is deliberately
        NOT folded in, it is non-monotone and re-checked in-scan).
    sc0:   (S,N) float32 chunk-start live scores (kernels.chunk_start_scores).

    Returns (cand (S,K) int32, thresh (S,)): the K best columns per row and
    the (K+1)-th value — the max score any node OUTSIDE the shortlist can
    ever reach during the chunk's scan, because a node's live score moves
    only when the node is debited, debits are tracked (touched nodes join
    the scan's candidate set), and untouched nodes keep sc0 exactly.
    -inf threshold ⇔ the shortlist already holds every feasible node.

    lax.top_k breaks ties toward the LOWER index — load-bearing for the
    scans' tie rule: every node outside the shortlist whose sc0 equals the
    threshold has a HIGHER index than every in-list node at that value, so
    an untouched in-list winner at exactly the threshold still wins the
    full scan's lowest-index tie-break.

    What is chunk-constant: all three of cand, thresh and each slot's
    ranked value where(feas0, sc0, -inf)[s, cand[s, k]]. A node's score
    and capacity move only when the node is debited, so until a scan
    debits it a slot is worth exactly what was ranked here — the same
    float, from the same evaluation that produced thresh. The W=1 scans
    read it back per step (sc0[cls, ci]); the wave scan takes it as a
    table made once a chunk (`sl_val`, _shortlist_wave_scan) and looks
    nothing up for an untouched slot. A debited node joins the scans'
    touched list and is evaluated live there from then on.
    """
    vals, cand = lax.top_k(jnp.where(feas0, sc0, NEG_INF), k + 1)
    return cand[:, :k].astype(jnp.int32), vals[:, k]


def block_bound_prefilter(alloc_q, used_nz_q, req_nz_q, static_scores,
                          feasible, fit_col_w, bal_col_mask, shape_u,
                          shape_s, w_fit, w_bal, strategy: str, n_real,
                          k: int, block_w: int):
    """Two-pass block-sparse form of the shortlist prefilter — the
    sublinear replacement for the full (C,N) `chunk_start_scores` +
    `shortlist_prefilter` pass at large N.

    Pass 1 (O(C·B)): fold the N node columns into B = ceil(N/block_w)
    fixed blocks, derive per-block aggregate planes IN-PROGRAM from the
    live capacity planes (never from maintained state — a mid-batch
    verify-reject fold-back decreases `used`, which would turn any
    chained max/min stale in the unsafe direction), and compute a per-
    (class, block) score upper bound (kernels.block_score_upper_bound).
    Select the M = 2·ceil((K+1)/block_w) highest-bound blocks per class.

    Pass 2 (O(C·M·block_w)): gather just the selected blocks' columns,
    score them with `kernels.gathered_start_scores` (bit-identical
    element arithmetic to the full pass — every op is element-wise over
    columns with reductions only over R), and take the per-class top-K
    + threshold exactly as `shortlist_prefilter` would.

    Exactness gate — the result is used ONLY when, for every class c
    and every non-selected block b, one arm holds:

    - strict:  ub[c,b] < t̂[c] — the bound (which over-approximates by
      construction, plus BLOCK_UB_EPS of float slack) already loses to
      the gathered (K+1)-th value, so no column of b can enter the
      top-K or move the threshold.
    - empty:   feas_cnt[c,b] == 0 — no feasible column at all.
    - uniform: block b lies strictly AFTER the last selected block,
      block b and that reference block are capacity-uniform and share
      one static score (exact tuple equality of (stat_max, stat_min,
      amin, amax, umin, umax) — no epsilon: identical inputs ⇒
      identical f32 outputs), and the reference block's best gathered
      value v_ref ≤ t̂. Then every feasible column of b scores exactly
      v_ref, and its position after the whole selection puts it at a
      higher global index than every gathered column, so at v_ref == t̂
      the full-width top_k's lower-index tie rule (see
      shortlist_prefilter) would still pick the gathered columns —
      threshold and candidates are bit-identical. This arm is what
      keeps uniform fleets (every node identical, every bound tied)
      prunable — the strict arm alone can never separate identical
      blocks — and because it keys on the last selected block rather
      than a fixed 0..M-1 prefix, it keeps firing as a drain's usage
      frontier advances and selection shifts to later blocks (the
      already-filled blocks behind the frontier prune via strict: their
      debited scores sit below the fresh-node threshold by more than
      BLOCK_UB_EPS for any non-trivial request). A uniform block before
      or between selected blocks cannot use this arm (its columns would
      WIN the ties) and routes to the fallback.

    When any block fails all arms, the whole chunk falls back to the
    full-width pass via lax.cond — exact by construction, and the
    fallback branch traces the r18/r21 call graph verbatim.

    Candidate caveat shared with the full prefilter: when a class has
    fewer than K feasible columns, the -inf candidate slots may name
    different (infeasible) columns than the full pass would — inert for
    the scans, which re-mask candidates against live feasibility.

    n_real: traced int32 — real (unpadded) node count; padding columns
    are excluded from every aggregate. k/block_w: static.

    Returns (sc0 (C,N) — gathered columns hold their exact chunk-start
    value, non-gathered columns are 0.0 and only ever read through the
    candidate set, cand (C,K), thresh (C,), blocks_scanned int32,
    blocks_pruned int32).
    """
    from kubernetes_tpu.ops import kernels  # local to avoid import cycle
    n = alloc_q.shape[0]
    c = static_scores.shape[0]
    bw = block_w
    b = -(-n // bw)
    m = 2 * (-(-(k + 1) // bw))
    if m + 1 > b:
        raise ValueError(
            f"block prefilter needs M+1={m + 1} <= B={b}; route "
            "block_w=0 for this shape (see AdaptiveTuner.block_width)")

    col_real = jnp.arange(n, dtype=jnp.int32) < n_real
    amin_pos, amin, amax, umin, umax = kernels.block_capacity_aggregates(
        alloc_q, used_nz_q, col_real, bw)
    stat_max, stat_min, feas_cnt = kernels.block_feasible_stat(
        feasible, static_scores, bw)
    ub = kernels.block_score_upper_bound(
        stat_max, feas_cnt, amin_pos, amax, umin, umax, req_nz_q,
        fit_col_w, bal_col_mask, shape_u, shape_s, w_fit, w_bal,
        strategy)                                                # (C,B)

    _, sel = lax.top_k(ub, m + 1)
    sel_ids = jnp.sort(sel[:, :m].astype(jnp.int32), axis=1)     # (C,M) asc
    rowi = jnp.arange(c, dtype=jnp.int32)[:, None]

    # Gather the selected blocks' columns. Ascending sel_ids keep the
    # gathered order a subsequence of global column order, so top_k's
    # lower-index tie rule below means the same thing it means full-width.
    cols = (sel_ids[:, :, None] * bw
            + jnp.arange(bw, dtype=jnp.int32)[None, None, :]).reshape(c, -1)
    valid = cols < n                    # tail fold-pad beyond the planes
    safe_cols = jnp.minimum(cols, n - 1)
    feas_g = jnp.take_along_axis(feasible, safe_cols, axis=1) & valid
    stat_g = jnp.take_along_axis(static_scores, safe_cols, axis=1)
    sc0_g = kernels.gathered_start_scores(
        alloc_q[safe_cols], used_nz_q[safe_cols], req_nz_q, stat_g,
        fit_col_w, bal_col_mask, shape_u, shape_s, w_fit, w_bal,
        strategy)                                                # (C,G)
    masked_g = jnp.where(feas_g, sc0_g, NEG_INF)
    vals, loc = lax.top_k(masked_g, k + 1)
    cand = jnp.take_along_axis(
        safe_cols, loc[:, :k], axis=1).astype(jnp.int32)
    thresh = vals[:, k]

    # Scatter gathered scores into a full-width sc0 row set the scans
    # can element-gather from. Invalid (fold-pad) lanes write to the
    # throwaway column N of an (N+1)-wide buffer — a clamp-to-N-1 write
    # would clobber the real last column.
    tgt = jnp.where(valid, cols, n)
    sc0_full = jnp.zeros((c, n + 1), jnp.float32).at[
        rowi, tgt].set(sc0_g)[:, :n]

    # --- exactness predicate over non-selected blocks ---
    is_sel = jnp.zeros((c, b), jnp.bool_).at[rowi, sel_ids].set(True)
    strict = ub < thresh[:, None]
    empty = feas_cnt == 0

    ref = sel_ids[:, m - 1:m]                                    # (C,1)
    unif_cap = (jnp.all(amin == amax, axis=1)
                & jnp.all(umin == umax, axis=1))[None, :]        # (1,B)
    stat_unif = stat_max == stat_min                             # (C,B)
    eq_cap = (jnp.all(amax[None, :, :] == amax[ref], axis=-1)
              & jnp.all(amin[None, :, :] == amin[ref], axis=-1)
              & jnp.all(umax[None, :, :] == umax[ref], axis=-1)
              & jnp.all(umin[None, :, :] == umin[ref], axis=-1))
    eq_stat = ((stat_max == jnp.take_along_axis(stat_max, ref, axis=1))
               & (stat_min == jnp.take_along_axis(stat_min, ref, axis=1)))
    # Only blocks strictly AFTER the last selected block qualify: their
    # columns all sit at higher global indices than every gathered
    # column, so ties at t̂ lose top_k's lower-index rule. A uniform
    # block BEFORE or BETWEEN selected blocks would win those ties —
    # it must prune via strict/empty or force the fallback.
    after_ref = jnp.arange(b, dtype=jnp.int32)[None, :] > ref
    v_ref = jnp.max(masked_g.reshape(c, m, bw)[:, m - 1, :], axis=-1)
    uniform = (after_ref & unif_cap & stat_unif & eq_cap & eq_stat
               & (v_ref <= thresh)[:, None])

    ok_all = jnp.all(is_sel | strict | empty | uniform)

    def _block_exact(_):
        return sc0_full, cand, thresh

    def _block_fallback_full(_):
        sc0 = kernels.chunk_start_scores(
            alloc_q, used_nz_q, req_nz_q, static_scores, fit_col_w,
            bal_col_mask, shape_u, shape_s, w_fit, w_bal, strategy)
        cand_f, thresh_f = shortlist_prefilter(feasible, sc0, k)
        return sc0, cand_f, thresh_f

    sc0_out, cand_out, thresh_out = lax.cond(
        ok_all, _block_exact, _block_fallback_full, jnp.int32(0))
    blocks_scanned = jnp.int32(c * b)
    blocks_pruned = jnp.where(ok_all, jnp.int32(c * (b - m)), jnp.int32(0))
    return sc0_out, cand_out, thresh_out, blocks_scanned, blocks_pruned


def _shortlist_scan(req_q, req_nz_q, rows, free_q, free_pods, used_nz_q,
                    alloc_q, mask, static_scores, fit_col_w, bal_col_mask,
                    shape_u, shape_s, w_fit, w_bal, strategy: str,
                    sc0, sl_class, sl_cand, sl_thresh, has_node,
                    inline_fallback: bool, exc=None, p_real=None):
    """The narrow sequential-equivalent scan: per pod, re-score only the
    pod's K shortlist columns plus every node already debited this chunk,
    and prove the winner exact against the prefilter threshold.

    Exactness argument, per step: nodes fall in three classes —
    (a) shortlist candidates and (b) nodes touched (debited) earlier in
    this chunk are both IN the candidate set and re-scored live; (c) an
    untouched node outside the shortlist still scores exactly its sc0
    ≤ thresh. So when the candidate-set winner's score beats `thresh`
    strictly — or ties it while itself untouched (see shortlist_prefilter
    on why the index tie-break then also goes the winner's way) — it is
    the full N-wide argmax. Otherwise the step falls back to the full row:
    inline via lax.cond when `inline_fallback` (single-order scans — the
    cond executes one branch), or by poisoning the whole scan when the
    caller runs under vmap (lax.cond would become a both-branches select
    there and the pruning would buy nothing).

    Untouched candidates gather their score from sc0 rather than
    recomputing it, so the `== thresh` comparison never straddles two
    float evaluations of the same quantity.

    sc0: (S,N) class-level chunk-start scores; sl_class: (P,) row index
    per pod (pods of one template share a class — and a shortlist);
    sl_cand: (P,K); sl_thresh: (P,); has_node: (P,) bool — pods whose
    static mask is empty (padding, unknown resources) trivially resolve
    to -1 with no fallback.

    `rows` (P,) maps each step to its pod's row in the UNPERMUTED
    mask/static_scores planes (class planes — (C, N); C == P in the
    per-pod degenerate form), which stay closed-over: the trusted path
    reads them through (row, ci) element gathers, never a row slice
    — an (N,)-wide xs row per step would put O(N) memory traffic back
    into the scan (and a permuted multistart copy would materialize the
    planes once per order). Only the fallback branch slices a full row,
    and only when taken. `exc` (optional (P,)) is the per-pod
    single-allowed-column exception: candidates outside it are
    infeasible for the pod, so a pinned pod whose column misses the
    class shortlist resolves through the bound-check fallback (all its
    candidates mask out → not trusted unless the shortlist already held
    every feasible class column).

    Returns (assign (P,), fallbacks int32, poisoned bool). With
    inline_fallback the assignment is exact and poisoned is always False;
    without it the assignment is only valid when poisoned is False.
    """
    from kubernetes_tpu.ops import kernels  # local to avoid import cycle

    n = free_q.shape[0]
    p = req_q.shape[0]
    iota_n = jnp.arange(n, dtype=jnp.int32)

    def step(carry, inp):
        free_q, free_pods, used_nz, touched, tidx, kstep, nfall, pois = carry
        if exc is None:
            req, req_nz, row, cand, t, cls, hn = inp
        else:
            req, req_nz, row, cand, t, cls, hn, e = inp
        cset = jnp.concatenate([cand, tidx])               # (K+P,)
        valid = cset < n
        ci = jnp.where(valid, cset, 0)
        live = static_scores[row, ci]
        live = live + w_fit * kernels.fit_score(
            alloc_q[ci], used_nz[ci], req_nz[None, :], fit_col_w, strategy,
            shape_u, shape_s)[0]
        live = live + w_bal * kernels.balanced_allocation_score(
            alloc_q[ci], used_nz[ci], req_nz[None, :], bal_col_mask)[0]
        live = jnp.where(touched[ci], live, sc0[cls, ci])
        fits = mask[row, ci] & valid \
            & jnp.all(req[None, :] <= free_q[ci], axis=1) \
            & (free_pods[ci] >= 1)
        if exc is not None:
            fits = fits & ((e < 0) | (ci == e))
        masked = jnp.where(fits, live, NEG_INF)
        best = jnp.max(masked)
        any_fit = best > NEG_INF
        widx = jnp.min(jnp.where(masked == best, ci, n)).astype(jnp.int32)
        w_touched = touched[jnp.minimum(widx, n - 1)]
        trusted = jnp.where(
            any_fit,
            (best > t) | ((best == t) & jnp.logical_not(w_touched)),
            t == NEG_INF) | jnp.logical_not(hn)
        sl_idx = jnp.where(any_fit, widx, jnp.int32(-1))
        if inline_fallback:
            def full_row(_):
                fits_n = mask[row] & jnp.all(req[None, :] <= free_q, axis=1) \
                    & (free_pods >= 1)
                if exc is not None:
                    fits_n = fits_n & ((e < 0) | (iota_n == e))
                sc = static_scores[row]
                sc = sc + w_fit * kernels.fit_score(
                    alloc_q, used_nz, req_nz[None, :], fit_col_w, strategy,
                    shape_u, shape_s)[0]
                sc = sc + w_bal * kernels.balanced_allocation_score(
                    alloc_q, used_nz, req_nz[None, :], bal_col_mask)[0]
                mk = jnp.where(fits_n, sc, NEG_INF)
                i2 = jnp.argmax(mk).astype(jnp.int32)
                return jnp.where(jnp.any(fits_n), i2, jnp.int32(-1))

            idx = lax.cond(trusted, lambda _: sl_idx, full_row, None)
        else:
            idx = sl_idx
            pois = pois | jnp.logical_not(trusted)
        nfall = nfall + jnp.logical_not(trusted).astype(jnp.int32)
        # Scatter updates (O(R), not O(N·R) — the whole point is that no
        # per-step work scales with N on the trusted path).
        hit = idx >= 0
        safe = jnp.clip(idx, 0, n - 1)
        free_q = free_q.at[safe].add(
            jnp.where(hit, -req, 0).astype(free_q.dtype))
        free_pods = free_pods.at[safe].add(
            jnp.where(hit, -1, 0).astype(free_pods.dtype))
        used_nz = used_nz.at[safe].add(
            jnp.where(hit, req_nz, 0).astype(used_nz.dtype))
        touched = touched.at[safe].set(touched[safe] | hit)
        tidx = tidx.at[kstep].set(jnp.where(hit, idx, n))
        return (free_q, free_pods, used_nz, touched, tidx, kstep + 1,
                nfall, pois), idx

    carry0 = (free_q, free_pods, used_nz_q,
              jnp.zeros((n,), jnp.bool_),
              jnp.full((p,), n, jnp.int32),
              jnp.int32(0), jnp.int32(0), jnp.bool_(False))
    xs = (req_q, req_nz_q, rows, sl_cand, sl_thresh, sl_class, has_node)
    if exc is not None:
        xs = xs + (exc,)
    (_, _, _, _, _, _, nfall, pois), assign = _scan_real(
        step, carry0, xs, p_real)
    return assign, nfall, pois


@partial(jax.jit, static_argnames=("strategy",))
def greedy_assign_rescoring_shortlist(req_q, req_nz_q, free_q, free_pods,
                                      used_nz_q, alloc_q, mask,
                                      static_scores, fit_col_w, bal_col_mask,
                                      shape_u, shape_s, w_fit, w_bal,
                                      strategy: str,
                                      sc0, sl_class, sl_cand, sl_thresh,
                                      has_node, rows=None, exc=None,
                                      p_real=None):
    """greedy_assign_rescoring, shortlist-pruned: bit-identical assignments
    at O(P·(K+P)) with per-step inline fallback to the full N-wide row
    (the lax.cond executes one branch — fallbacks cost O(N) only when
    taken). Returns (assign (P,), fallbacks int32)."""
    if rows is None:
        rows = jnp.arange(req_q.shape[0], dtype=jnp.int32)
    assign, nfall, _ = _shortlist_scan(
        req_q, req_nz_q, rows, free_q, free_pods, used_nz_q, alloc_q, mask,
        static_scores, fit_col_w, bal_col_mask, shape_u, shape_s,
        w_fit, w_bal, strategy, sc0, sl_class, sl_cand, sl_thresh,
        has_node, inline_fallback=True, exc=exc, p_real=p_real)
    return assign, nfall


@partial(jax.jit, static_argnames=("strategy",))
def multistart_greedy_assign_shortlist(req_q, req_nz_q, free_q, free_pods,
                                       used_nz_q, alloc_q, mask,
                                       static_scores, fit_col_w,
                                       bal_col_mask, shape_u, shape_s,
                                       w_fit, w_bal, strategy: str, perms,
                                       gang_onehot, gang_required,
                                       sc0, sl_class, sl_cand, sl_thresh,
                                       has_node, rows=None, exc=None,
                                       p_real=None):
    """multistart_greedy_assign, shortlist-pruned.

    The K permuted scans run vmapped, so a per-step lax.cond would lower
    to a both-branches select and re-pay the N-wide row every step — the
    narrow scans instead mark any step whose bound check fails as
    POISONED, and one outer lax.cond (not vmapped — a real branch) reruns
    the whole chunk through the full multistart when any order was
    poisoned. Shortlist/threshold are chunk-start state, so they are
    permutation-independent; only per-pod rows reorder.

    Returns (assign (P,), fallback_pods int32) — fallback accounting is
    whole-chunk here (P on a poisoned chunk, 0 otherwise)."""
    P = req_q.shape[0]
    arange_p = jnp.arange(P, dtype=jnp.int32)
    if rows is None:
        rows = arange_p

    def one(perm):
        # Only the small per-pod vectors permute; the class planes stay
        # unpermuted and the scan addresses them through `rows[perm]` —
        # permuting them here would materialize one copy per order.
        a, _, pois = _shortlist_scan(
            req_q[perm], req_nz_q[perm], rows[perm], free_q, free_pods,
            used_nz_q, alloc_q, mask, static_scores, fit_col_w,
            bal_col_mask, shape_u, shape_s, w_fit, w_bal, strategy,
            sc0, sl_class[perm], sl_cand[perm], sl_thresh[perm],
            has_node[perm], inline_fallback=False,
            exc=None if exc is None else exc[perm], p_real=p_real)
        inv = jnp.zeros_like(perm).at[perm].set(arange_p)
        return a[inv], pois

    assigns, pois = jax.vmap(one)(perms)
    any_pois = jnp.any(pois)

    def full(_):
        return _multistart_body(
            req_q, req_nz_q, free_q, free_pods, used_nz_q, alloc_q, mask,
            static_scores, fit_col_w, bal_col_mask, shape_u, shape_s,
            w_fit, w_bal, strategy, perms, gang_onehot, gang_required,
            rows, exc, p_real)

    def take(_):
        return _select_best(assigns, req_q, gang_onehot, gang_required)

    assign = lax.cond(any_pois, full, take, None)
    return assign, jnp.where(any_pois, jnp.int32(P), jnp.int32(0))


@partial(jax.jit, static_argnames=("strategy",))
def greedy_assign_rescoring_spread_shortlist(
        req_q, req_nz_q, free_q, free_pods, used_nz_q, alloc_q, mask,
        static_scores, fit_col_w, bal_col_mask, shape_u, shape_s,
        w_fit, w_bal, strategy: str,
        dom_onehot, cid_onehot, dom_counts, max_skew, min_ok, has_key_nc,
        applies, contributes,
        sc0, sl_class, sl_cand, sl_thresh, has_node, rows=None, exc=None,
        p_real=None):
    """greedy_assign_rescoring_spread, shortlist-pruned (identity order,
    inline per-step fallback like the non-spread scan).

    Spread gating is non-monotone (a domain can open as the global min
    rises), so it deliberately plays no part in the prefilter: shortlist
    and threshold are capacity/mask/score-only — an outside node's SCORE
    is still bounded by the threshold whatever its gating does, and the
    in-scan candidate set applies the exact per-step gate. Conservative
    only: a pod whose allowed domains all sit outside its score head
    falls back to the full row.

    Returns (assign (P,), dom_counts', fallbacks int32)."""
    from kubernetes_tpu.ops import kernels  # local to avoid import cycle

    n = free_q.shape[0]
    p = req_q.shape[0]
    big = jnp.float32(1e30)
    iota_n = jnp.arange(n, dtype=jnp.int32)
    in_dom_nc = (dom_onehot @ cid_onehot) > 0                          # (N,C)
    gate_nc = has_key_nc > 0

    rows_p = jnp.arange(p, dtype=jnp.int32) if rows is None else rows

    def step(carry, inp):
        (free_q, free_pods, used_nz, dcounts, touched, tidx, kstep,
         nfall) = carry
        if exc is None:
            req, req_nz, row, app, contrib, cand, t, cls, hn = inp
        else:
            req, req_nz, row, app, contrib, cand, t, cls, hn, e = inp
        min_c = jnp.min(
            jnp.where(cid_onehot > 0, dcounts[:, None], big), axis=0)  # (C,)
        min_c = min_c * min_ok
        self_d = cid_onehot @ contrib                                  # (D,)
        allowed_d = (dcounts + self_d - cid_onehot @ min_c) \
            <= (cid_onehot @ max_skew)                                 # (D,)
        allowed_dc = allowed_d[:, None] * cid_onehot                   # (D,C)

        cset = jnp.concatenate([cand, tidx])
        valid = cset < n
        ci = jnp.where(valid, cset, 0)
        in_allowed_c = (dom_onehot[ci] @ allowed_dc) > 0               # (C',C)
        node_ok_c = gate_nc[ci] & (
            in_allowed_c | jnp.logical_not(in_dom_nc[ci]))
        spread_ok_c = jnp.all(node_ok_c | (app[None, :] == 0), axis=1)
        live = static_scores[row, ci]
        live = live + w_fit * kernels.fit_score(
            alloc_q[ci], used_nz[ci], req_nz[None, :], fit_col_w, strategy,
            shape_u, shape_s)[0]
        live = live + w_bal * kernels.balanced_allocation_score(
            alloc_q[ci], used_nz[ci], req_nz[None, :], bal_col_mask)[0]
        live = jnp.where(touched[ci], live, sc0[cls, ci])
        fits = mask[row, ci] & valid & spread_ok_c \
            & jnp.all(req[None, :] <= free_q[ci], axis=1) \
            & (free_pods[ci] >= 1)
        if exc is not None:
            fits = fits & ((e < 0) | (ci == e))
        masked = jnp.where(fits, live, NEG_INF)
        best = jnp.max(masked)
        any_fit = best > NEG_INF
        widx = jnp.min(jnp.where(masked == best, ci, n)).astype(jnp.int32)
        w_touched = touched[jnp.minimum(widx, n - 1)]
        trusted = jnp.where(
            any_fit,
            (best > t) | ((best == t) & jnp.logical_not(w_touched)),
            t == NEG_INF) | jnp.logical_not(hn)
        sl_idx = jnp.where(any_fit, widx, jnp.int32(-1))

        def full_row(_):
            in_allowed = (dom_onehot @ allowed_dc) > 0
            node_c_ok = gate_nc & (in_allowed | jnp.logical_not(in_dom_nc))
            spread_ok = jnp.all(node_c_ok | (app[None, :] == 0), axis=1)
            fits_n = mask[row] & jnp.all(req[None, :] <= free_q, axis=1) \
                & (free_pods >= 1) & spread_ok
            if exc is not None:
                fits_n = fits_n & ((e < 0) | (iota_n == e))
            sc = static_scores[row]
            sc = sc + w_fit * kernels.fit_score(
                alloc_q, used_nz, req_nz[None, :], fit_col_w, strategy,
                shape_u, shape_s)[0]
            sc = sc + w_bal * kernels.balanced_allocation_score(
                alloc_q, used_nz, req_nz[None, :], bal_col_mask)[0]
            mk = jnp.where(fits_n, sc, NEG_INF)
            i2 = jnp.argmax(mk).astype(jnp.int32)
            return jnp.where(jnp.any(fits_n), i2, jnp.int32(-1))

        idx = lax.cond(trusted, lambda _: sl_idx, full_row, None)
        nfall = nfall + jnp.logical_not(trusted).astype(jnp.int32)
        hit = idx >= 0
        safe = jnp.clip(idx, 0, n - 1)
        free_q = free_q.at[safe].add(
            jnp.where(hit, -req, 0).astype(free_q.dtype))
        free_pods = free_pods.at[safe].add(
            jnp.where(hit, -1, 0).astype(free_pods.dtype))
        used_nz = used_nz.at[safe].add(
            jnp.where(hit, req_nz, 0).astype(used_nz.dtype))
        # Same accounting as the full spread scan's `hit @ dom_onehot`,
        # via one row gather instead of an O(N·D) reduce.
        dcounts = dcounts + jnp.where(
            hit, dom_onehot[safe] * (cid_onehot @ contrib), 0.0)
        touched = touched.at[safe].set(touched[safe] | hit)
        tidx = tidx.at[kstep].set(jnp.where(hit, idx, n))
        return (free_q, free_pods, used_nz, dcounts, touched, tidx,
                kstep + 1, nfall), idx

    carry0 = (free_q, free_pods, used_nz_q, dom_counts,
              jnp.zeros((n,), jnp.bool_),
              jnp.full((p,), n, jnp.int32),
              jnp.int32(0), jnp.int32(0))
    xs = (req_q, req_nz_q, rows_p, applies, contributes,
          sl_cand, sl_thresh, sl_class, has_node)
    if exc is not None:
        xs = xs + (exc,)
    (_, _, _, dom_counts2, _, _, _, nfall), assign = _scan_real(
        step, carry0, xs, p_real)
    return assign, dom_counts2, nfall


# ---------------------------------------------------------------------------
# Speculative wavefront scans: W pods per scan step with exact conflict
# replay. The serial scans above are bound by their LENGTH — P sequential
# steps, each a chain of small ops — while the r14 class planes make a
# W-wide evaluation of the same step nearly as cheap as a 1-wide one.
#
# Per wave step:
#   1. evaluate all W pods against the same carry state (one (W,·) pass
#      over the closed-over class planes);
#   2. pick PREFIX-DISTINCT speculative choices: member w takes the best
#      node not picked by members 0..w-1 (max score, lowest node index
#      among ties — the serial argmax rule over the not-yet-debited set);
#   3. prove each speculation serial-equivalent with a pairwise conflict
#      check: member w's pick stands iff no earlier member's committed
#      node, RE-SCORED after its own debit, would beat member w's pick
#      under the serial (score, lowest-index) order. Debits usually only
#      lower a node's score (LeastAllocated), but not always (a debit can
#      RAISE MostAllocated/BalancedAllocation scores and serial greedy
#      then re-picks the same node) — the check re-scores instead of
#      assuming monotonicity, so speculation is exact by proof, not hope;
#   4. commit the whole wave's debits in one scatter when no member
#      conflicts; otherwise REPLAY the wave serially (lax.fori_loop of
#      the one-pod step body) — reproducing the serial order exactly.
#
# Untouched nodes keep bitwise-identical scores across a wave (the score
# kernels are elementwise per node), so the only nodes whose serial value
# can differ from the wave evaluation are the ≤W wave commits — exactly
# the set the pairwise check re-scores. Assignments are therefore
# bit-identical to the W=1 scans at every wave width; only the replay
# fraction (observability, tuner feedback) is workload-dependent.
# ---------------------------------------------------------------------------


def _wave_split(wave_w: int, arrays):
    """Pad the pod axis to a multiple of wave_w and reshape each array to
    (n_waves, wave_w, ...) for wave-by-wave scanning. Returns the reshaped
    arrays plus the matching real-pod mask (padding members never fit,
    never commit, never conflict) and the padded length."""
    p = arrays[0].shape[0]
    pad = (-p) % wave_w
    out = []
    for a in arrays:
        if pad:
            a = jnp.concatenate(
                [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)], axis=0)
        out.append(a.reshape((-1, wave_w) + a.shape[1:]))
    real = (jnp.arange(p + pad, dtype=jnp.int32) < p).reshape(-1, wave_w)
    return out, real, p + pad


def _wave_live(p_real, p: int, wave_w: int, n_waves: int):
    """What the chunk's real pod count `p_real` (traced int32; None = p)
    means to a wave scan over `p` pods in `n_waves` waves of `wave_w`.
    Padding sits at the chunk's end, so real members are a prefix.

    Returns (steps, live, idle): `steps` — the waves that hold a real
    pod, the scan's trip count; `live` (n_waves,) — real members of each
    wave, the serial replay's trip count; `idle` — the members of the
    waves the scan skips. A wave of padding commits whole without placing
    anything, so the commit counter gets `idle` added back and reads what
    the full-length scan reads."""
    p_real = jnp.int32(p) if p_real is None else p_real
    steps = (p_real + (wave_w - 1)) // wave_w
    live = jnp.clip(
        p_real - wave_w * jnp.arange(n_waves, dtype=jnp.int32), 0, wave_w)
    idle = p - jnp.minimum(steps * wave_w, p)
    return steps, live, idle


def _wave_spec_picks(masked, node_of, nbig, wave_w: int):
    """Prefix-distinct speculative picks for one wave.

    masked: (W, M) candidate scores with NEG_INF = infeasible; node_of:
    (W, M) int32 global node id per slot (slots may repeat a node — the
    shortlist candidate set does); nbig: "no pick" sentinel greater than
    every node id. Member w's pick is the max value over slots whose node
    no earlier member picked, resolved to the LOWEST node id among ties —
    the serial argmax rule over the not-yet-debited nodes. The loop is
    unrolled (W is static) over tiny fused compares; no top-k is involved,
    so tie resolution is exact even when many slots share the max value.

    Returns (b (W,) f32 scores, y (W,) int32 node ids, nbig = no pick).
    """
    bs, ys = [], []
    for w in range(wave_w):
        row = masked[w]
        for yp in ys:
            row = jnp.where(node_of[w] == yp, NEG_INF, row)
        b = jnp.max(row)
        y = jnp.min(jnp.where(row == b, node_of[w], nbig))
        ys.append(jnp.where(b > NEG_INF, y, nbig).astype(jnp.int32))
        bs.append(b)
    return jnp.stack(bs), jnp.stack(ys)


def _wave_conflicts(b, y, nbig, req, req_nz, free_q, free_pods, used_nz,
                    alloc_q, m_pair, stat_pair, fit_col_w, bal_col_mask,
                    shape_u, shape_s, w_fit, w_bal, strategy,
                    extra_ok=None):
    """(W,) conflict bits: member w's speculative pick is invalidated by
    an earlier member's commit in the same wave.

    For each committed node y_j (j < w), re-score it FOR POD w after pod
    j's debit (used_nz[y_j] + req_nz_j, free[y_j] - req_j) with the same
    elementwise kernels the serial step uses; member w conflicts iff some
    y_j stays feasible for it and beats its pick under the serial order —
    strictly higher score, or equal score at a lower node index. A member
    with no pick (b = -inf) conflicts whenever any earlier commit is
    still feasible for it (serial might place it there). `m_pair`
    (W, W): pod w's static mask at node y_j; `stat_pair` (W, W): pod w's
    capacity-independent score at y_j; `extra_ok` optionally folds a
    variant-specific gate (spread) into feasibility. Prefix-distinct
    picks never collide, so node identity conflicts cannot occur — only
    score movement on debited nodes can, and that is exactly what is
    re-checked.
    """
    from kubernetes_tpu.ops import kernels  # local to avoid import cycle

    W = y.shape[0]
    n = free_q.shape[0]
    hit = y < nbig
    safe = jnp.minimum(y, n - 1)
    fr_j = free_q[safe] - req                                  # (W,R)
    fp_j = free_pods[safe] - 1                                 # (W,)
    unz_j = used_nz[safe] + req_nz                             # (W,R)
    al_j = alloc_q[safe]
    upd = stat_pair + w_fit * kernels.fit_score(
        al_j, unz_j, req_nz, fit_col_w, strategy, shape_u, shape_s)
    upd = upd + w_bal * kernels.balanced_allocation_score(
        al_j, unz_j, req_nz, bal_col_mask)                     # (W,W)
    cap = jnp.all(req[:, None, :] <= fr_j[None, :, :], axis=-1)
    feas = m_pair & cap & (fp_j >= 1)[None, :] & hit[None, :]
    if extra_ok is not None:
        feas = feas & extra_ok
    beats = feas & ((upd > b[:, None])
                    | ((upd == b[:, None]) & (y[None, :] < y[:, None])))
    w_iota = jnp.arange(W, dtype=jnp.int32)
    tri = w_iota[None, :] < w_iota[:, None]                    # j < w
    return jnp.any(beats & tri, axis=1)


def _rescoring_wave_scan(req_q, req_nz_q, free_q, free_pods, used_nz_q,
                         alloc_q, mask, static_scores, fit_col_w,
                         bal_col_mask, shape_u, shape_s, w_fit, w_bal,
                         strategy: str, wave_w: int, rows, exc,
                         poison: bool, p_real=None):
    """Traceable wavefront core of greedy_assign_rescoring.

    poison=False: conflicted waves take the in-step serial replay branch
    (a real lax.cond — only taken waves pay it), so the result is exact.
    poison=True is the vmapped-multistart shape (a cond under vmap lowers
    to a both-branches select, re-paying the serial wave every step):
    speculation always commits, the first conflict POISONS the scan, and
    the caller discards poisoned results — same contract as the shortlist
    multistart. Returns (assign, commits, replays, poisoned)."""
    from kubernetes_tpu.ops import kernels  # local to avoid import cycle

    n = free_q.shape[0]
    p = req_q.shape[0]
    W = max(1, min(wave_w, p))
    iota_n = jnp.arange(n, dtype=jnp.int32)
    ex = jnp.full((p,), -1, jnp.int32) if exc is None else exc
    (req_w, req_nz_w, rows_w, ex_w), real_w, _ = _wave_split(
        W, (req_q, req_nz_q, rows, ex))
    steps, live_w, idle = _wave_live(p_real, p, W, real_w.shape[0])

    def wave_step(carry, inp):
        free_q, free_pods, used_nz, ncom, nrep, pois = carry
        req, req_nz, row, e, real, live = inp
        m = mask[row]                                          # (W,N)
        m = m & ((e < 0)[:, None] | (iota_n[None, :] == e[:, None]))
        m = m & real[:, None]
        fits = m & jnp.all(req[:, None, :] <= free_q[None, :, :], axis=-1) \
            & (free_pods >= 1)[None, :]
        sc = static_scores[row]
        sc = sc + w_fit * kernels.fit_score(
            alloc_q, used_nz, req_nz, fit_col_w, strategy, shape_u, shape_s)
        sc = sc + w_bal * kernels.balanced_allocation_score(
            alloc_q, used_nz, req_nz, bal_col_mask)
        masked = jnp.where(fits, sc, NEG_INF)
        node_of = jnp.broadcast_to(iota_n[None, :], masked.shape)
        b, y = _wave_spec_picks(masked, node_of, n, W)
        safe = jnp.minimum(y, n - 1)
        conflict = _wave_conflicts(
            b, y, n, req, req_nz, free_q, free_pods, used_nz, alloc_q,
            m[:, safe], static_scores[row[:, None], safe[None, :]],
            fit_col_w, bal_col_mask, shape_u, shape_s, w_fit, w_bal,
            strategy)
        nreal = jnp.sum(real.astype(jnp.int32))

        def fast(st):
            fq, fp, unz, nc, nr, po = st
            hit = y < n
            fq = fq.at[safe].add(
                jnp.where(hit[:, None], -req, 0).astype(fq.dtype))
            fp = fp.at[safe].add(jnp.where(hit, -1, 0).astype(fp.dtype))
            unz = unz.at[safe].add(
                jnp.where(hit[:, None], req_nz, 0).astype(unz.dtype))
            return (fq, fp, unz, nc + nreal, nr, po), \
                jnp.where(hit, y, jnp.int32(-1))

        if poison:
            carry2, out = fast((free_q, free_pods, used_nz, ncom, nrep,
                                pois | jnp.any(conflict)))
            return carry2, out

        def slow(st):
            fq, fp, unz, nc, nr, po = st

            def body(w, s):
                fq, fp, unz, out = s
                rq, rnz = req[w], req_nz[w]
                fits_w = m[w] & jnp.all(rq[None, :] <= fq, axis=1) \
                    & (fp >= 1)
                scw = static_scores[row[w]]
                scw = scw + w_fit * kernels.fit_score(
                    alloc_q, unz, rnz[None, :], fit_col_w, strategy,
                    shape_u, shape_s)[0]
                scw = scw + w_bal * kernels.balanced_allocation_score(
                    alloc_q, unz, rnz[None, :], bal_col_mask)[0]
                mk = jnp.where(fits_w, scw, NEG_INF)
                idx = jnp.argmax(mk).astype(jnp.int32)
                idx = jnp.where(jnp.any(fits_w), idx, jnp.int32(-1))
                hitw = idx >= 0
                sf = jnp.clip(idx, 0, n - 1)
                fq = fq.at[sf].add(jnp.where(hitw, -rq, 0).astype(fq.dtype))
                fp = fp.at[sf].add(jnp.where(hitw, -1, 0).astype(fp.dtype))
                unz = unz.at[sf].add(
                    jnp.where(hitw, rnz, 0).astype(unz.dtype))
                return (fq, fp, unz, out.at[w].set(idx))

            fq, fp, unz, out = lax.fori_loop(
                0, live, body, (fq, fp, unz, jnp.full((W,), -1, jnp.int32)))
            return (fq, fp, unz, nc, nr + nreal, po), out

        return lax.cond(jnp.any(conflict), slow, fast,
                        (free_q, free_pods, used_nz, ncom, nrep, pois))

    carry0 = (free_q, free_pods, used_nz_q, jnp.int32(0), jnp.int32(0),
              jnp.bool_(False))
    (_, _, _, ncom, nrep, pois), out = _scan_real(
        wave_step, carry0, (req_w, req_nz_w, rows_w, ex_w, real_w, live_w),
        steps, (W,))
    return out.reshape(-1)[:p], ncom + idle, nrep, pois


@partial(jax.jit, static_argnames=("strategy", "wave_w"))
def greedy_assign_rescoring_wave(req_q, req_nz_q, free_q, free_pods,
                                 used_nz_q, alloc_q, mask, static_scores,
                                 fit_col_w, bal_col_mask, shape_u, shape_s,
                                 w_fit, w_bal, strategy: str, wave_w: int,
                                 rows=None, exc=None, p_real=None):
    """greedy_assign_rescoring, W pods per scan step (see the wavefront
    section comment for the speculation/replay contract). Assignments are
    bit-identical to the W=1 scan at every wave_w; wave_w=1 runs the
    degenerate one-member wave. Returns (assign (P,), commits int32,
    replays int32) — the commit/replay split is the tuner's feedback
    signal (replays are exact but serial)."""
    if rows is None:
        rows = jnp.arange(req_q.shape[0], dtype=jnp.int32)
    assign, ncom, nrep, _ = _rescoring_wave_scan(
        req_q, req_nz_q, free_q, free_pods, used_nz_q, alloc_q, mask,
        static_scores, fit_col_w, bal_col_mask, shape_u, shape_s,
        w_fit, w_bal, strategy, wave_w, rows, exc, poison=False,
        p_real=p_real)
    return assign, ncom, nrep


@partial(jax.jit, static_argnames=("strategy", "wave_w"))
def multistart_greedy_assign_wave(req_q, req_nz_q, free_q, free_pods,
                                  used_nz_q, alloc_q, mask, static_scores,
                                  fit_col_w, bal_col_mask, shape_u, shape_s,
                                  w_fit, w_bal, strategy: str, wave_w: int,
                                  perms, gang_onehot, gang_required,
                                  rows=None, exc=None, p_real=None):
    """multistart_greedy_assign with wavefront scans under the vmap.

    The K permuted scans run vmapped, so the per-wave replay cond would
    lower to a both-branches select — instead every order runs
    speculation-only and POISONS on its first conflict, and one outer
    lax.cond (a real branch) reruns the whole chunk through the W=1
    multistart when any order was poisoned (the shortlist-multistart
    contract). Returns (assign (P,), commits int32, replays int32);
    counters are whole-chunk on the poisoned path (P replays)."""
    P = req_q.shape[0]
    arange_p = jnp.arange(P, dtype=jnp.int32)
    if rows is None:
        rows = arange_p

    def one(perm):
        a, _, _, pois = _rescoring_wave_scan(
            req_q[perm], req_nz_q[perm], free_q, free_pods, used_nz_q,
            alloc_q, mask, static_scores, fit_col_w, bal_col_mask,
            shape_u, shape_s, w_fit, w_bal, strategy, wave_w, rows[perm],
            None if exc is None else exc[perm], poison=True, p_real=p_real)
        inv = jnp.zeros_like(perm).at[perm].set(arange_p)
        return a[inv], pois

    assigns, pois = jax.vmap(one)(perms)
    any_pois = jnp.any(pois)

    def full(_):
        return _multistart_body(
            req_q, req_nz_q, free_q, free_pods, used_nz_q, alloc_q, mask,
            static_scores, fit_col_w, bal_col_mask, shape_u, shape_s,
            w_fit, w_bal, strategy, perms, gang_onehot, gang_required,
            rows, exc, p_real)

    def take(_):
        return _select_best(assigns, req_q, gang_onehot, gang_required)

    assign = lax.cond(any_pois, full, take, None)
    ncom = jnp.where(any_pois, jnp.int32(0), jnp.int32(P))
    nrep = jnp.where(any_pois, jnp.int32(P), jnp.int32(0))
    return assign, ncom, nrep


@partial(jax.jit, static_argnames=("strategy", "wave_w"))
def greedy_assign_rescoring_spread_wave(req_q, req_nz_q, free_q, free_pods,
                                        used_nz_q, alloc_q, mask,
                                        static_scores, fit_col_w,
                                        bal_col_mask, shape_u, shape_s,
                                        w_fit, w_bal, strategy: str,
                                        wave_w: int,
                                        dom_onehot, cid_onehot, dom_counts,
                                        max_skew, min_ok, has_key_nc,
                                        applies, contributes, rows=None,
                                        exc=None, p_real=None):
    """greedy_assign_rescoring_spread, W pods per scan step with per-wave
    domain-count updates.

    Spread gating is NON-monotone in the carry — a commit that moves a
    domain count can OPEN another domain for later pods (the global-min
    rise), so an earlier commit can change a later member's feasible SET
    upward, which the capacity/score conflict check cannot see. The
    conflict predicate therefore adds the exact structural rule: member w
    replays whenever any earlier member committed a placement that moves
    any domain count (contributes to any constraint) AND member w carries
    a gating constraint itself; gate-free members (applies all-zero) ride
    the capacity/score rule alone, with the wave-start spread gate folded
    into the pairwise feasibility. Domain counts commit per wave (exact:
    counts are small integers in f32, addition order immaterial).
    Returns (assign (P,), dom_counts', commits, replays)."""
    from kubernetes_tpu.ops import kernels  # local to avoid import cycle

    n = free_q.shape[0]
    p = req_q.shape[0]
    W = max(1, min(wave_w, p))
    big = jnp.float32(1e30)
    iota_n = jnp.arange(n, dtype=jnp.int32)
    in_dom_nc = (dom_onehot @ cid_onehot) > 0                          # (N,C)
    gate_nc = has_key_nc > 0
    if rows is None:
        rows = jnp.arange(p, dtype=jnp.int32)
    ex = jnp.full((p,), -1, jnp.int32) if exc is None else exc
    (req_w, req_nz_w, rows_w, app_w, con_w, ex_w), real_w, _ = _wave_split(
        W, (req_q, req_nz_q, rows, applies, contributes, ex))
    steps, live_w, idle = _wave_live(p_real, p, W, real_w.shape[0])

    def spread_gate(dcounts, contrib, app):
        """(W,N) DoNotSchedule gate at the given counts — the serial
        step's gate, batched over the wave (each member folds its own
        selfMatch term)."""
        min_c = jnp.min(
            jnp.where(cid_onehot > 0, dcounts[:, None], big), axis=0)
        min_c = min_c * min_ok                                         # (C,)
        self_d = contrib @ cid_onehot.T                                # (W,D)
        allowed_d = (dcounts[None, :] + self_d
                     - (cid_onehot @ min_c)[None, :]) \
            <= (cid_onehot @ max_skew)[None, :]                        # (W,D)
        in_allowed = jnp.einsum(
            "nd,wdc->wnc", dom_onehot,
            allowed_d[:, :, None] * cid_onehot[None, :, :]) > 0        # (W,N,C)
        node_c_ok = gate_nc[None, :, :] \
            & (in_allowed | jnp.logical_not(in_dom_nc)[None, :, :])
        return jnp.all(node_c_ok | (app[:, None, :] == 0), axis=2)     # (W,N)

    def wave_step(carry, inp):
        free_q, free_pods, used_nz, dcounts, ncom, nrep = carry
        req, req_nz, row, app, contrib, e, real, live = inp
        m = mask[row]
        m = m & ((e < 0)[:, None] | (iota_n[None, :] == e[:, None]))
        m = m & real[:, None]
        sp_ok = spread_gate(dcounts, contrib, app)                     # (W,N)
        fits = m & sp_ok \
            & jnp.all(req[:, None, :] <= free_q[None, :, :], axis=-1) \
            & (free_pods >= 1)[None, :]
        sc = static_scores[row]
        sc = sc + w_fit * kernels.fit_score(
            alloc_q, used_nz, req_nz, fit_col_w, strategy, shape_u, shape_s)
        sc = sc + w_bal * kernels.balanced_allocation_score(
            alloc_q, used_nz, req_nz, bal_col_mask)
        masked = jnp.where(fits, sc, NEG_INF)
        node_of = jnp.broadcast_to(iota_n[None, :], masked.shape)
        b, y = _wave_spec_picks(masked, node_of, n, W)
        safe = jnp.minimum(y, n - 1)
        hit = y < n
        conflict = _wave_conflicts(
            b, y, n, req, req_nz, free_q, free_pods, used_nz, alloc_q,
            m[:, safe], static_scores[row[:, None], safe[None, :]],
            fit_col_w, bal_col_mask, shape_u, shape_s, w_fit, w_bal,
            strategy, extra_ok=sp_ok[:, safe])
        # The structural non-monotonicity rule: any earlier count-moving
        # commit forces gated members into the serial replay.
        movers = hit & jnp.any(contrib > 0, axis=1)                    # (W,)
        earlier_moved = jnp.cumsum(movers.astype(jnp.int32)) \
            - movers.astype(jnp.int32) > 0
        conflict = conflict | (earlier_moved & jnp.any(app > 0, axis=1))
        nreal = jnp.sum(real.astype(jnp.int32))

        def fast(st):
            fq, fp, unz, dc, nc, nr = st
            fq = fq.at[safe].add(
                jnp.where(hit[:, None], -req, 0).astype(fq.dtype))
            fp = fp.at[safe].add(jnp.where(hit, -1, 0).astype(fp.dtype))
            unz = unz.at[safe].add(
                jnp.where(hit[:, None], req_nz, 0).astype(unz.dtype))
            add = jnp.where(hit[:, None],
                            dom_onehot[safe] * (contrib @ cid_onehot.T),
                            0.0)                                       # (W,D)
            dc = dc + jnp.sum(add, axis=0)
            return (fq, fp, unz, dc, nc + nreal, nr), \
                jnp.where(hit, y, jnp.int32(-1))

        def slow(st):
            fq, fp, unz, dc, nc, nr = st

            def body(w, s):
                fq, fp, unz, dc, out = s
                rq, rnz = req[w], req_nz[w]
                sp_w = spread_gate(dc, contrib[w][None, :],
                                   app[w][None, :])[0]
                fits_w = m[w] & sp_w \
                    & jnp.all(rq[None, :] <= fq, axis=1) & (fp >= 1)
                scw = static_scores[row[w]]
                scw = scw + w_fit * kernels.fit_score(
                    alloc_q, unz, rnz[None, :], fit_col_w, strategy,
                    shape_u, shape_s)[0]
                scw = scw + w_bal * kernels.balanced_allocation_score(
                    alloc_q, unz, rnz[None, :], bal_col_mask)[0]
                mk = jnp.where(fits_w, scw, NEG_INF)
                idx = jnp.argmax(mk).astype(jnp.int32)
                idx = jnp.where(jnp.any(fits_w), idx, jnp.int32(-1))
                hitw = idx >= 0
                sf = jnp.clip(idx, 0, n - 1)
                fq = fq.at[sf].add(jnp.where(hitw, -rq, 0).astype(fq.dtype))
                fp = fp.at[sf].add(jnp.where(hitw, -1, 0).astype(fp.dtype))
                unz = unz.at[sf].add(
                    jnp.where(hitw, rnz, 0).astype(unz.dtype))
                dc = dc + jnp.where(
                    hitw, dom_onehot[sf] * (cid_onehot @ contrib[w]), 0.0)
                return (fq, fp, unz, dc, out.at[w].set(idx))

            fq, fp, unz, dc, out = lax.fori_loop(
                0, live, body,
                (fq, fp, unz, dc, jnp.full((W,), -1, jnp.int32)))
            return (fq, fp, unz, dc, nc, nr + nreal), out

        return lax.cond(jnp.any(conflict), slow, fast,
                        (free_q, free_pods, used_nz, dcounts, ncom, nrep))

    carry0 = (free_q, free_pods, used_nz_q, dom_counts,
              jnp.int32(0), jnp.int32(0))
    (_, _, _, dom_counts2, ncom, nrep), out = _scan_real(
        wave_step, carry0,
        (req_w, req_nz_w, rows_w, app_w, con_w, ex_w, real_w, live_w),
        steps, (W,))
    return out.reshape(-1)[:p], dom_counts2, ncom + idle, nrep


def _per_row(table, idx, n_rows: int, wave_w: int):
    """`table(r)` at each wave member's row `idx` (W,), where `table` maps
    a vector of row ids (G,) to a (G, ...) table of per-row lookups.

    Both shapes are static. When there are no more rows than wave members
    (class planes and class shortlists: a handful of rows) the table is
    evaluated once per ROW and the members read their row — a gather of W
    contiguous rows — instead of once per member: the lookups behind it
    fall from W·M to n_rows·M. The per-pod degenerate form (n_rows == P,
    `class_split_fallbacks`) evaluates the W members' rows directly and
    never builds an (n_rows, ·) table."""
    if n_rows <= wave_w:
        return table(jnp.arange(n_rows, dtype=jnp.int32))[idx]
    return table(idx)


def _shortlist_wave_scan(req_q, req_nz_q, rows, free_q, free_pods,
                         used_nz_q, alloc_q, mask, static_scores, fit_col_w,
                         bal_col_mask, shape_u, shape_s, w_fit, w_bal,
                         strategy: str, wave_w: int,
                         sl_class, sl_cand, sl_val, sl_thresh, has_node,
                         poison: bool, exc=None, p_real=None):
    """_shortlist_scan with W pods per wave step.

    The wave evaluates each member's candidate set (its top-K shortlist ∪
    every node debited this chunk) against the same carry, takes
    prefix-distinct picks, and speculation must clear BOTH proofs:

    - the shortlist bound check (the W=1 `trusted` rule verbatim): the
      pick beats the prefilter threshold, so no node OUTSIDE the
      candidate set can be the serial winner;
    - the pairwise wave check (_wave_conflicts): no same-wave earlier
      commit, re-scored after its debit, beats the pick — covering the
      nodes whose serial value moved since the wave evaluation.

    A member failing either falls into the serial replay, which runs the
    full N-wide row (exact regardless of why the bound failed); replays
    count into `fallbacks` — they pay the same O(N) a W=1 bound-check
    fallback pays. poison semantics as _rescoring_wave_scan (the vmapped
    multistart shape). Returns (assign, fallbacks, commits, replays,
    poisoned).

    Each candidate is looked up ONCE — per chunk where it cannot change,
    per wave step where it can — never once per wave member (a TPU
    gathers element by element at scalar pace: the per-member form was
    8·W·(K+P) lookups a step and set the pace of the 50k drain):

    - chunk constants — the shortlist tables, one row per shortlist
      CLASS (`sl_class` (P,) maps a pod to its row; pods of a class share
      plane row and request, which is what makes them share a shortlist):
      `sl_cand` (S,K) node ids, `sl_thresh` (S,), and `sl_val` (S,K), each
      slot's masked value at chunk start: sc0 where the class is
      chunk-start feasible on the node (plane mask ∧ capacity fit), else
      -inf — the value `shortlist_prefilter` ranked. An UNTOUCHED node
      still has exactly that state, so its slot needs no lookup at all;
      and because the value is sc0's own float, the `== thresh`
      comparison never straddles two evaluations of one quantity (the
      W=1 rule). Also `mstat` (C,N), the mask folded into the static
      plane as -inf, so that one lookup reads both.
    - per step, once for the whole wave — the touched list `tidx` is
      shared by all members: node state at `tidx` is gathered once
      ((P_pad,) rows of alloc/used_nz/free/free_pods) and the W members
      are scored against it by broadcasting their request rows through
      the kernels the full-width scans call (elementwise per node:
      bitwise the W=1 values); `mstat` at `tidx` is read per plane row,
      then by member (`_per_row`).
    - a TOUCHED shortlist node sits in `tidx` with its live value, so its
      shortlist slot reads -inf (`_wave_spec_picks` resolves by node id;
      the two slots held the same value before). Touched-ness is a
      compare against `tidx`, per class row (`_per_row`), not a lookup.
    - per member, elementwise on the node ids only: the `exc` pin and the
      padding bit `real`.
    """
    from kubernetes_tpu.ops import kernels  # local to avoid import cycle

    n = free_q.shape[0]
    p = req_q.shape[0]
    W = max(1, min(wave_w, p))
    n_rows = mask.shape[0]
    n_cls = sl_cand.shape[0]
    iota_n = jnp.arange(n, dtype=jnp.int32)
    ex = jnp.full((p,), -1, jnp.int32) if exc is None else exc
    (req_w, req_nz_w, rows_w, cls_w, hn_w, ex_w), real_w, p_pad = \
        _wave_split(W, (req_q, req_nz_q, rows, sl_class, has_node, ex))
    steps, live_w, idle = _wave_live(p_real, p, W, real_w.shape[0])
    mstat = jnp.where(mask, static_scores, NEG_INF)             # (C,N)

    def wave_step(carry, inp):
        (free_q, free_pods, used_nz, tidx, kstep, nfall, ncom, nrep,
         pois) = carry
        req, req_nz, row, cls, hn, e, real, live = inp
        # Touched half: live, against node state gathered once.
        ti = jnp.minimum(tidx, n - 1)                           # (P_pad,)
        al_t, unz_t = alloc_q[ti], used_nz[ti]
        ms_t = _per_row(lambda r: mstat[r[:, None], ti[None, :]],
                        row, n_rows, W)                         # (W,P_pad)
        live_t = ms_t + w_fit * kernels.fit_score(
            al_t, unz_t, req_nz, fit_col_w, strategy, shape_u, shape_s)
        live_t = live_t + w_bal * kernels.balanced_allocation_score(
            al_t, unz_t, req_nz, bal_col_mask)
        fits_t = (ms_t > NEG_INF) & (tidx < n)[None, :] \
            & jnp.all(req[:, None, :] <= free_q[ti][None, :, :], axis=-1) \
            & (free_pods[ti] >= 1)[None, :]
        # Shortlist half: the chunk-start value unless the node was
        # touched since (tidx's sentinel n equals no node id).
        dead = _per_row(
            lambda s: jnp.any(
                sl_cand[s][:, :, None] == tidx[None, None, :], axis=-1),
            cls, n_cls, W)                                      # (W,K)
        node_of = jnp.concatenate(
            [sl_cand[cls], jnp.broadcast_to(tidx[None, :], (W, p_pad))],
            axis=1)                                             # (W,M)
        masked = jnp.concatenate(
            [jnp.where(dead, NEG_INF, sl_val[cls]),
             jnp.where(fits_t, live_t, NEG_INF)], axis=1)
        masked = jnp.where(
            ((e < 0)[:, None] | (node_of == e[:, None])) & real[:, None],
            masked, NEG_INF)
        b, y = _wave_spec_picks(masked, node_of, n, W)
        safe = jnp.minimum(y, n - 1)
        hit = y < n
        # The W=1 trusted rule on each member's pick (chunk-touched
        # status at wave start; picks are never same-wave commits).
        t = sl_thresh[cls]
        y_touched = jnp.any(y[:, None] == tidx[None, :], axis=1)
        trusted = jnp.where(
            hit,
            (b > t) | ((b == t) & jnp.logical_not(y_touched)),
            t == NEG_INF) | jnp.logical_not(hn)
        ms_pair = _per_row(lambda r: mstat[r[:, None], safe[None, :]],
                           row, n_rows, W)                      # (W,W)
        conflict = jnp.logical_not(trusted) | _wave_conflicts(
            b, y, n, req, req_nz, free_q, free_pods, used_nz, alloc_q,
            (ms_pair > NEG_INF)
            & ((e < 0)[:, None] | (safe[None, :] == e[:, None]))
            & real[:, None],
            ms_pair, fit_col_w, bal_col_mask, shape_u, shape_s, w_fit,
            w_bal, strategy)
        nreal = jnp.sum(real.astype(jnp.int32))

        def fast(st):
            (fq, fp, unz, tix, ks, nf, nc, nr, po) = st
            fq = fq.at[safe].add(
                jnp.where(hit[:, None], -req, 0).astype(fq.dtype))
            fp = fp.at[safe].add(jnp.where(hit, -1, 0).astype(fp.dtype))
            unz = unz.at[safe].add(
                jnp.where(hit[:, None], req_nz, 0).astype(unz.dtype))
            tix = lax.dynamic_update_slice(
                tix, jnp.where(hit, y, n), (ks,))
            return (fq, fp, unz, tix, ks + W, nf, nc + nreal, nr,
                    po), jnp.where(hit, y, jnp.int32(-1))

        if poison:
            carry2, out = fast(
                (free_q, free_pods, used_nz, tidx, kstep, nfall, ncom,
                 nrep, pois | jnp.any(conflict)))
            return carry2, out

        def slow(st):
            (fq, fp, unz, tix, ks, nf, nc, nr, po) = st

            def body(w, s):
                fq, fp, unz, tix, out = s
                rq, rnz = req[w], req_nz[w]
                fits_n = mask[row[w]] & real[w] \
                    & jnp.all(rq[None, :] <= fq, axis=1) & (fp >= 1) \
                    & ((e[w] < 0) | (iota_n == e[w]))
                scw = static_scores[row[w]]
                scw = scw + w_fit * kernels.fit_score(
                    alloc_q, unz, rnz[None, :], fit_col_w, strategy,
                    shape_u, shape_s)[0]
                scw = scw + w_bal * kernels.balanced_allocation_score(
                    alloc_q, unz, rnz[None, :], bal_col_mask)[0]
                mk = jnp.where(fits_n, scw, NEG_INF)
                idx = jnp.argmax(mk).astype(jnp.int32)
                idx = jnp.where(jnp.any(fits_n), idx, jnp.int32(-1))
                hitw = idx >= 0
                sf = jnp.clip(idx, 0, n - 1)
                fq = fq.at[sf].add(jnp.where(hitw, -rq, 0).astype(fq.dtype))
                fp = fp.at[sf].add(jnp.where(hitw, -1, 0).astype(fp.dtype))
                unz = unz.at[sf].add(
                    jnp.where(hitw, rnz, 0).astype(unz.dtype))
                tix = tix.at[ks + w].set(jnp.where(hitw, idx, n))
                return (fq, fp, unz, tix, out.at[w].set(idx))

            fq, fp, unz, tix, out = lax.fori_loop(
                0, live, body,
                (fq, fp, unz, tix, jnp.full((W,), -1, jnp.int32)))
            return (fq, fp, unz, tix, ks + W, nf + nreal, nc,
                    nr + nreal, po), out

        return lax.cond(
            jnp.any(conflict), slow, fast,
            (free_q, free_pods, used_nz, tidx, kstep, nfall, ncom, nrep,
             pois))

    carry0 = (free_q, free_pods, used_nz_q,
              jnp.full((p_pad,), n, jnp.int32),
              jnp.int32(0), jnp.int32(0), jnp.int32(0), jnp.int32(0),
              jnp.bool_(False))
    (_, _, _, _, _, nfall, ncom, nrep, pois), out = _scan_real(
        wave_step, carry0,
        (req_w, req_nz_w, rows_w, cls_w, hn_w, ex_w, real_w, live_w),
        steps, (W,))
    return out.reshape(-1)[:p], nfall, ncom + idle, nrep, pois


@partial(jax.jit, static_argnames=("strategy", "wave_w"))
def greedy_assign_rescoring_shortlist_wave(req_q, req_nz_q, free_q,
                                           free_pods, used_nz_q, alloc_q,
                                           mask, static_scores, fit_col_w,
                                           bal_col_mask, shape_u, shape_s,
                                           w_fit, w_bal, strategy: str,
                                           wave_w: int,
                                           sl_class, sl_cand, sl_val,
                                           sl_thresh, has_node, rows=None,
                                           exc=None, p_real=None):
    """greedy_assign_rescoring_shortlist with wavefront waves: exact via
    the in-step serial replay (full N-wide rows, counted as fallbacks).
    The shortlist arrives as CLASS tables (sl_cand/sl_val (S,K),
    sl_thresh (S,)) addressed through sl_class (P,) — see
    _shortlist_wave_scan. Returns (assign (P,), fallbacks, commits,
    replays)."""
    if rows is None:
        rows = jnp.arange(req_q.shape[0], dtype=jnp.int32)
    assign, nfall, ncom, nrep, _ = _shortlist_wave_scan(
        req_q, req_nz_q, rows, free_q, free_pods, used_nz_q, alloc_q,
        mask, static_scores, fit_col_w, bal_col_mask, shape_u, shape_s,
        w_fit, w_bal, strategy, wave_w, sl_class, sl_cand, sl_val,
        sl_thresh, has_node, poison=False, exc=exc, p_real=p_real)
    return assign, nfall, ncom, nrep


@partial(jax.jit, static_argnames=("strategy", "wave_w"))
def multistart_greedy_assign_shortlist_wave(req_q, req_nz_q, free_q,
                                            free_pods, used_nz_q, alloc_q,
                                            mask, static_scores, fit_col_w,
                                            bal_col_mask, shape_u, shape_s,
                                            w_fit, w_bal, strategy: str,
                                            wave_w: int, perms,
                                            gang_onehot, gang_required,
                                            sl_class, sl_cand, sl_val,
                                            sl_thresh, has_node, rows=None,
                                            exc=None, p_real=None):
    """multistart_greedy_assign_shortlist with wavefront waves under the
    vmap: each order runs speculation-only and poisons on its first wave
    conflict OR failed bound check; one outer lax.cond reruns the whole
    chunk through the W=1 full multistart when any order was poisoned.
    The class shortlist tables are chunk-start state, so they are
    permutation-independent and stay unpermuted; only the per-pod
    vectors (sl_class among them) reorder.
    Returns (assign (P,), fallback_pods, commits, replays) — fallback
    and replay accounting is whole-chunk here, like the W=1 variant."""
    P = req_q.shape[0]
    arange_p = jnp.arange(P, dtype=jnp.int32)
    if rows is None:
        rows = arange_p

    def one(perm):
        a, _, _, _, pois = _shortlist_wave_scan(
            req_q[perm], req_nz_q[perm], rows[perm], free_q, free_pods,
            used_nz_q, alloc_q, mask, static_scores, fit_col_w,
            bal_col_mask, shape_u, shape_s, w_fit, w_bal, strategy, wave_w,
            sl_class[perm], sl_cand, sl_val, sl_thresh, has_node[perm],
            poison=True, exc=None if exc is None else exc[perm],
            p_real=p_real)
        inv = jnp.zeros_like(perm).at[perm].set(arange_p)
        return a[inv], pois

    assigns, pois = jax.vmap(one)(perms)
    any_pois = jnp.any(pois)

    def full(_):
        return _multistart_body(
            req_q, req_nz_q, free_q, free_pods, used_nz_q, alloc_q, mask,
            static_scores, fit_col_w, bal_col_mask, shape_u, shape_s,
            w_fit, w_bal, strategy, perms, gang_onehot, gang_required,
            rows, exc, p_real)

    def take(_):
        return _select_best(assigns, req_q, gang_onehot, gang_required)

    assign = lax.cond(any_pois, full, take, None)
    nfall = jnp.where(any_pois, jnp.int32(P), jnp.int32(0))
    ncom = jnp.where(any_pois, jnp.int32(0), jnp.int32(P))
    nrep = jnp.where(any_pois, jnp.int32(P), jnp.int32(0))
    return assign, nfall, ncom, nrep

def _solve_one_core(alloc_q, used_pack, alloc_pods, taint_f_mat,
                    taint_p_mat, mask_bits, host_scores, req_pack,
                    fit_col_w, bal_col_mask, shape_u, shape_s,
                    w_fit, w_bal, w_taint, taint_filter_on, strategy):
    """Traceable body shared by solve_one / solve_one_fresh."""
    from kubernetes_tpu.ops import kernels  # local to avoid import cycle

    n = alloc_q.shape[0]
    r = alloc_q.shape[1]
    tf = taint_f_mat.shape[1]
    # Wire decompression, identical to _mask_solve_update's unpack.
    shifts = jnp.arange(7, -1, -1, dtype=jnp.uint8)
    cmask = ((mask_bits[:, None] >> shifts) & 1).reshape(-1) \
        .astype(jnp.bool_)[:n]
    used_q = used_pack[:, :r]
    used_nz = used_pack[:, r:2 * r]
    used_pods = used_pack[:, 2 * r]
    req_q = req_pack[None, :r]
    req_nz = req_pack[None, r:2 * r]
    untol_f = req_pack[2 * r:2 * r + tf].astype(jnp.bool_)[None]
    untol_p = req_pack[2 * r + tf:].astype(jnp.bool_)[None]

    fit0 = kernels.fit_filter_mask(
        alloc_q, used_q, used_pods, alloc_pods, req_q)          # (1,N)
    taint_ok = kernels.taint_filter_mask(taint_f_mat, untol_f)
    taint_ok = taint_ok | jnp.logical_not(taint_filter_on)
    mask = cmask[None, :] & taint_ok
    feasible = mask & fit0
    static = host_scores[None, :].astype(jnp.float32) \
        + w_taint * kernels.taint_toleration_score(
            taint_p_mat, untol_p, feasible)

    # The scan step body for pod 0: chunk-start free state IS the
    # current state for a single-pod "chunk".
    free_q = alloc_q - used_q
    free_pods = alloc_pods - used_pods
    fits = mask[0] & jnp.all(req_q[0][None, :] <= free_q, axis=1) \
        & (free_pods >= 1)
    sc = static[0]
    sc = sc + w_fit * kernels.fit_score(
        alloc_q, used_nz, req_nz, fit_col_w, strategy, shape_u, shape_s)[0]
    sc = sc + w_bal * kernels.balanced_allocation_score(
        alloc_q, used_nz, req_nz, bal_col_mask)[0]
    masked = jnp.where(fits, sc, NEG_INF)
    idx = jnp.argmax(masked).astype(jnp.int32)
    return jnp.where(jnp.any(fits), idx, jnp.int32(-1))


@partial(jax.jit, static_argnames=("strategy",))
def solve_one(alloc_q, used_pack, alloc_pods, taint_f_mat, taint_p_mat,
              mask_bits, host_scores, req_pack,
              fit_col_w, bal_col_mask, shape_u, shape_s,
              w_fit, w_bal, w_taint, taint_filter_on, strategy: str):
    """One pod against the resident cluster planes, bit-identical to the
    batch path's first scan step.

    This is deliberately the EXACT composition `_mask_solve_update` +
    `greedy_assign_rescoring` compute for the first pod of a chunk — the
    same kernels in the same order on the same dtypes — so a lone pod
    routed here by the serving tier's admission window gets the
    assignment the batch path would have given it (the smoke suite's
    randomized differential pins it). What is REMOVED is everything a
    lone pod cannot use: the P-step scan, multistart permutation set,
    shortlist prefilter/top-k, gang masks, spread carry, per-chunk plane
    build. The program is fixed-shape per (N, R, T) cluster signature,
    so after the first compile a placement is one dispatch.

    mask_bits: (N/8,) uint8 bit-packed host filter row (the pod's AND-
        folded static rows; all-true for the common template pod).
    host_scores: (N,) f16/f32 host score row (zero for the common pod —
        cast to f32 on device exactly like the batch wire).
    req_pack: (2R+tf+tp,) int32 — req_q ‖ req_nz_q ‖ untol_f ‖ untol_p,
        the class_pack row of this pod's equivalence class.
    used_pack: (N, 2R+1) int32 resident used-state (used_q ‖ used_nz_q ‖
        used_pods) — the serving tier keeps it warm on device and
        refreshes O(changed) rows from the cache's dirty set.

    Returns the node index as an int32 scalar (-1 = no fit). There is
    deliberately NO debit output: the placement's assume re-enters
    through the cache's dirty set and the next refresh re-quantizes
    that one row — a debited pack here would be dead work per solve
    (and double-count against the refresh).
    """
    return _solve_one_core(
        alloc_q, used_pack, alloc_pods, taint_f_mat, taint_p_mat,
        mask_bits, host_scores, req_pack, fit_col_w, bal_col_mask,
        shape_u, shape_s, w_fit, w_bal, w_taint, taint_filter_on, strategy)


@partial(jax.jit, static_argnames=("strategy",))
def solve_one_fresh(alloc_q, used_pack, rows, vals, alloc_pods,
                    taint_f_mat, taint_p_mat, mask_bits, host_scores,
                    req_pack, fit_col_w, bal_col_mask, shape_u, shape_s,
                    w_fit, w_bal, w_taint, taint_filter_on, strategy: str):
    """solve_one with the resident-plane refresh FUSED in: scatter the
    dirty rows (`vals` re-quantized host-side, rows bucket-padded by
    repeating the first index — idempotent) into the resident pack,
    then solve against the refreshed state — ONE device dispatch where
    refresh-then-solve was two, which is most of the fast path's wall
    on a local device. Returns (idx, refreshed_pack): the caller keeps
    the refreshed (PRE-debit) pack as the new resident base — the
    solve's own assume re-enters through the cache's dirty set, so
    debiting here would double-count it on the next refresh."""
    pack = used_pack.at[rows].set(vals)
    idx = _solve_one_core(
        alloc_q, pack, alloc_pods, taint_f_mat, taint_p_mat,
        mask_bits, host_scores, req_pack, fit_col_w, bal_col_mask,
        shape_u, shape_s, w_fit, w_bal, w_taint, taint_filter_on, strategy)
    return idx, pack


#: int32 "no victim" priority padding — mirrors _WaveState.INF (int64 there;
#: the device scan runs int32, and k8s priorities are int32 by API).
PRIO_INF = jnp.int32(2**31 - 1)


@jax.jit
def propose_victims(req_q, prio, banned, used, alloc, pods_used, pods_alloc,
                    vreq, vprio, offsets):
    """Batched preemption victim proposal (SURVEY §7 phase 6,
    "solve-with-victim-relaxation"): ONE device program proposes, for every
    failed preemptor in a wave, the reference-cost-minimal (node, victim
    count) — replacing the per-preemptor host candidate search.

    Per node, victims are the priority-ASCENDING resident prefix (the same
    ordering `DefaultPreemption._WaveState` builds), so "evict the first k"
    is always the cheapest k-victim set and prefix feasibility is a
    relaxed-capacity check. The scan threads claims through per-node state
    exactly like the capacity carry in `greedy_assign`: a chosen node's
    victim prefix is consumed (shifted out) and the preemptor's load is
    charged, so concurrent preemptors spread instead of stacking — the
    in-wave accounting `_WaveState.claim` does, but without P host
    round-trips.

    req_q:    (P, R) int32 preemptor requests, wave order (priority desc)
    prio:     (P,)   int32 preemptor priorities
    banned:   (P, N) bool  — UnschedulableAndUnresolvable nodes per preemptor
    used/alloc:        (N, R) int32 node requested/allocatable
    pods_used/alloc:   (N,)   int32
    vreq:     (N, K, R) int32 per-victim requests (ascending priority; 0 pad)
    vprio:    (N, K)    int32 per-victim priorities (PRIO_INF pad)
    offsets:  (P,) int32 per-preemptor rotation for the equal-cost tiebreak
        (the host path's seeded tie shuffle, made deterministic: ties pick
        the node minimizing (index - offset) mod N, so a wave's preemptors
        spread across an equal-cost set instead of all hitting node 0)

    Returns (node (P,) int32 [-1 = no candidate], count (P,) int32,
    used', pods_used', vreq', vprio') — the post-claim carry, so a caller
    chunking a wave wider than one P bucket threads state across calls
    without re-uploading (same pattern as the packed used-state chain).

    Cost ordering per the reference's pickOneNodeForPreemption subset the
    host path implements: lowest max victim priority → smallest priority
    sum → fewest victims (PDB tier absent there too). Proposals are
    host-verified against the live snapshot (full Filter chain) before any
    eviction — this program only replaces the SEARCH.
    """
    N, K, R = vreq.shape
    iota_n = jnp.arange(N, dtype=jnp.int32)
    karange = jnp.arange(K, dtype=jnp.int32)
    BIG = jnp.int32(2**31 - 1)

    def step(carry, inp):
        used, pods_used, vreq, vprio = carry
        q, p, ban, off = inp
        valid = vprio < PRIO_INF                            # (N, K)
        rel = jnp.cumsum(vreq, axis=1)                      # (N, K, R)
        prio_m = jnp.where(valid, vprio, 0)
        # Priority SUM rides float32: an int32 cumsum of near-INT32_MAX
        # priorities over a deep prefix overflows. Exact below 2^24;
        # above, the sum key coarsens ties only — candidates are
        # host-verified before any eviction either way.
        vsum = jnp.cumsum(prio_m.astype(jnp.float32), axis=1)
        vmax = lax.cummax(prio_m, axis=1)                   # (N, K)
        # Ascending sort ⇒ vprio[k] < p implies the whole prefix is
        # below the preemptor (same invariant the host candidates() uses).
        eligible = vprio < p
        fits = jnp.all(used[:, None, :] - rel + q[None, None, :]
                       <= alloc[:, None, :], axis=-1)
        fits = fits & (pods_used[:, None] - karange[None, :]
                       <= pods_alloc[:, None])
        ok = eligible & fits                                # (N, K)
        any_ok = jnp.any(ok, axis=1) & jnp.logical_not(ban)
        kmin = jnp.argmax(ok, axis=1).astype(jnp.int32)     # first fit
        cmax = jnp.take_along_axis(vmax, kmin[:, None], 1)[:, 0]
        csum = jnp.take_along_axis(vsum, kmin[:, None], 1)[:, 0]
        # Staged lexicographic argmin (vmax, vsum, count), rotation tiebreak.
        k1 = jnp.where(any_ok, cmax, BIG)
        c1 = any_ok & (cmax == jnp.min(k1))
        k2 = jnp.where(c1, csum, jnp.float32(jnp.inf))
        c2 = c1 & (csum == jnp.min(k2))
        k3 = jnp.where(c2, kmin, BIG)
        c3 = c2 & (kmin == jnp.min(k3))
        rot = (iota_n - off) % N
        n_star = jnp.argmin(jnp.where(c3, rot, BIG)).astype(jnp.int32)
        found = jnp.any(any_ok)
        count = kmin[n_star] + 1
        # Claim: drop the chosen prefix, charge the preemptor, shift the
        # node's victim arrays so later wave members see the truth.
        hit = (iota_n == n_star) & found
        freed = rel[n_star, count - 1]                      # (R,)
        used = used + jnp.where(hit[:, None], q[None, :] - freed[None, :], 0)
        pods_used = pods_used + jnp.where(hit, 1 - count, 0)
        src = jnp.clip(karange + count, 0, K - 1)
        keep = (karange + count) < K
        row_vreq = jnp.where(keep[:, None], vreq[n_star][src], 0)
        row_vprio = jnp.where(keep, vprio[n_star][src], PRIO_INF)
        vreq = jnp.where(hit[:, None, None], row_vreq[None, :, :], vreq)
        vprio = jnp.where(hit[:, None], row_vprio[None, :], vprio)
        out = (jnp.where(found, n_star, jnp.int32(-1)),
               jnp.where(found, count, jnp.int32(0)))
        return (used, pods_used, vreq, vprio), out

    carry, (node, count) = lax.scan(
        step, (used, pods_used, vreq, vprio),
        (req_q, prio, banned, offsets))
    return (node, count) + carry


@jax.jit
def fragmentation(free_q, alloc_q, valid):
    """Node fragmentation %: mean over non-empty resource columns of the
    free/allocatable fraction on nodes that host at least one pod would
    over-estimate; the metric BASELINE tracks is simpler — mean remaining
    capacity fraction across valid nodes (lower = tighter packing)."""
    alloc = alloc_q.astype(jnp.float32)
    frac = jnp.where(alloc > 0, free_q.astype(jnp.float32) / alloc, 0.0)
    per_node = jnp.sum(frac, axis=1) / jnp.maximum(
        jnp.sum(alloc > 0, axis=1), 1)
    return 100.0 * jnp.sum(jnp.where(valid, per_node, 0.0)) / jnp.maximum(
        jnp.sum(valid), 1)


@jax.jit
def fragmentation_occupied(free_q, alloc_q, used_pods, valid):
    """OCCUPIED-node fragmentation %: mean free-capacity fraction over
    nodes hosting at least one pod. This is the r20 optimizable metric —
    the all-nodes `fragmentation` above is placement-INVARIANT once every
    pod places (total free capacity is fixed by the workload), while this
    variant rewards concentrating load: packing the same pods onto fewer,
    fuller nodes lowers it, spreading them raises it. 0 occupied nodes →
    0.0 (an empty cluster is not fragmented)."""
    occ = valid & (used_pods > 0)
    alloc = alloc_q.astype(jnp.float32)
    frac = jnp.where(alloc > 0, free_q.astype(jnp.float32) / alloc, 0.0)
    per_node = jnp.sum(frac, axis=1) / jnp.maximum(
        jnp.sum(alloc > 0, axis=1), 1)
    return 100.0 * jnp.sum(jnp.where(occ, per_node, 0.0)) / jnp.maximum(
        jnp.sum(occ), 1)


#: annealing stages of the Sinkhorn temperature schedule (4T → 2T → T).
SINKHORN_STAGES = 3


@jax.jit
def sinkhorn_plan(feasible, cost, row_counts, col_cap, iters, temp):
    """Entropic-regularized transport plan over the (C, N) class planes
    (the r20 batch-optimal solve mode — SURVEY §5's Sinkhorn row).

    The class dictionary is what makes this affordable: the cost matrix
    is C×N (pod equivalence classes × nodes), never P×N, so the whole
    iteration runs on device planes that already exist. Marginals:

    - row_counts (C,) f32 — pods per class this chunk (the row mass each
      class must place; padding rides the reserved EMPTY class whose
      all-false feasible row zeros its kernel row).
    - col_cap (N,) — remaining pod slots per node, an INEQUALITY bound:
      the column step caps column mass at capacity (the partial-transport
      update v = min(1, b/col)) rather than forcing columns full, so
      under-capacity nodes simply receive less mass.

    Costs are the greedy scorer's own chunk-start scores (the warm
    start), shifted per row so one temperature means the same thing at
    any score scale. Temperature ANNEALS over SINKHORN_STAGES stages
    (4T → 2T → T): early high-temperature rounds spread mass and settle
    the capacity duals, late low-temperature rounds sharpen toward the
    assignment vertex. `iters`/`temp` are traced (live KTPU_SINKHORN_ITERS
    / KTPU_SINKHORN_TEMP knobs, no recompile); the loop lowers to a while.

    Returns (log_plan (C,N) f32, plan (C,N) f32). log_plan is sanitized
    (-1e30 on infeasible/non-finite entries) so it drops directly into
    the scans as `static_scores` for the feasibility-preserving rounding
    pass; monotone per row, so the rounding argmax ranks by plan mass.
    On uniform workloads the plan ties across equal columns and the
    rounding degenerates to first-fit — which is exactly the packing
    behavior the occupied-fragmentation metric rewards.
    """
    a = row_counts.astype(jnp.float32)
    b = jnp.maximum(col_cap.astype(jnp.float32), 0.0)
    eps = jnp.float32(1e-12)
    n_iters = jnp.maximum(iters, 1)
    stages = jnp.int32(SINKHORN_STAGES)
    kmask = feasible.astype(jnp.float32)
    # Row-relative costs: subtract each row's feasible max so exp() is
    # bounded in (0, 1] and `temp` is scale-free.
    rmax = jnp.max(jnp.where(feasible, cost.astype(jnp.float32), NEG_INF),
                   axis=1, keepdims=True)
    sc = jnp.where(feasible, cost.astype(jnp.float32) - rmax, 0.0)

    def kernel(stage):
        t = temp * jnp.exp2((stages - 1 - stage).astype(jnp.float32))
        return kmask * jnp.exp(sc / jnp.maximum(t, eps))

    def step(i, uv):
        u, v = uv
        k = kernel(jnp.minimum((stages * i) // n_iters, stages - 1))
        u = a / jnp.maximum(k @ v, eps)
        col = u @ k
        v = jnp.minimum(jnp.float32(1.0), b / jnp.maximum(col, eps))
        return (u, v)

    u, v = lax.fori_loop(
        0, n_iters, step,
        (jnp.ones(a.shape, jnp.float32), jnp.ones(b.shape, jnp.float32)))
    plan = u[:, None] * kernel(stages - 1) * v[None, :]
    log_plan = jnp.log(plan + jnp.float32(1e-30))
    log_plan = jnp.where(jnp.isfinite(log_plan) & feasible, log_plan,
                         jnp.float32(-1e30))
    return log_plan, plan


@jax.jit
def consolidation_scores(free_q, alloc_q, used_pods, valid, threshold):
    """Per-node consolidation priority for the descheduler, scored from
    the same resident device planes the solver consumes: occupied nodes
    whose mean free-capacity fraction is ≥ `threshold` are drain
    candidates, scored by emptiness (emptiest first — draining the node
    with the least to move frees a whole node soonest). Empty nodes,
    invalid rows, and well-packed nodes score NEG_INF (never drained)."""
    alloc = alloc_q.astype(jnp.float32)
    frac = jnp.where(alloc > 0, free_q.astype(jnp.float32) / alloc, 0.0)
    per_node = jnp.sum(frac, axis=1) / jnp.maximum(
        jnp.sum(alloc > 0, axis=1), 1)
    eligible = valid & (used_pods > 0) & (per_node >= threshold)
    return jnp.where(eligible, per_node, NEG_INF)
