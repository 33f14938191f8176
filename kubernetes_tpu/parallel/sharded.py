"""Mesh-sharded variants of the batched scheduling step.

Two phases, mirroring ops/backend._mask_and_solve exactly (same inputs, same
split of capacity-independent vs live-rescored score components):

1. `sharded_masks_scores` — the (P×N) mask + static-score phase under `jit`
   with `NamedSharding` constraints on a 2-D (pods × nodes) mesh: pure data
   parallelism, XLA inserts no collectives beyond layout changes. This is
   the DP×TP-analog fan-out replacing the reference's 16-goroutine
   `parallelize.Until` (SURVEY §2.8 row 1). Returns (mask, feasible,
   static_scores) where static_scores = host rows + weighted taint score —
   the capacity-independent components only; fit/balanced are re-scored
   live inside the solver.

2. `sharded_greedy_assign` — the sequential-equivalent solver under
   `shard_map` over the nodes axis: node state (free capacity, scores) lives
   sharded; each scan step computes its shard-local best candidate and
   resolves the global winner with `pmax`/`pmin` over ICI — the cross-shard
   argmax reduction pattern of SURVEY §5.7. Pod vectors are replicated
   (they're O(R) small). The winning shard debits its local capacity; the
   chosen index is identical on every shard by construction.

Both are mesh-size-agnostic (a (1,)-mesh degrades to the single-chip path)
and compile once per (mesh, strategy) — jitted programs are cached on the
hashable Mesh itself, with scalar weights as traced arguments.

Pairing with the sharded CONTROL plane (store/sharded.py, r13): the
per-shard host prep maintains the node axis in GLOBAL order (hash shards
own scattered row sets, never reordered), so the arrays these solvers
consume are the same ones the single-store path produces — the device
mesh is free to block-partition that axis over chips while the control
plane hash-partitions it over stores, and the per-step `pmax`/`pmin`
winner reduction below IS the cross-shard argmax of both decompositions
(assignments stay bit-identical to the unsharded path by the index tie
rule; tests/test_sharded_parity.py pins it end to end).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kubernetes_tpu.ops import kernels, pallas_kernel, solver
from kubernetes_tpu.parallel.mesh import NODES_AXIS, PODS_AXIS, SLICE_AXIS

_INT_MAX = jnp.int32(2**31 - 1)

_PHASE_CACHE: dict = {}
_SOLVER_CACHE: dict = {}


def _block_w_for(block_w: int, shortlist_k: int, local_n: int) -> int:
    """Clamp a requested block-index width to a shard-local shape it is
    valid for: the two-pass prefilter needs M+1 ≤ B over the SHARD'S
    column count (ops/solver.block_bound_prefilter's static guard — a
    shard too narrow to leave one block unselected has nothing to
    prune). 0 keeps the full-width local prefilter, structurally."""
    if not (block_w and shortlist_k):
        return 0
    b = -(-local_n // block_w)
    m = 2 * (-(-(shortlist_k + 1) // block_w))
    return block_w if m + 1 <= b else 0


# ---------------------------------------------------------------------------
# phase 1: masks + static scores (2-D pods × nodes mesh)
# ---------------------------------------------------------------------------

def sharded_masks_scores(mesh: Mesh, alloc_q, used_q, used_nz_q, alloc_pods,
                         used_pods, req_q, req_nz_q, untol_f, untol_p,
                         taint_f_mat, taint_p_mat, static_mask, host_scores,
                         w_taint, taint_filter_on: bool, strategy: str):
    """Mask + capacity-independent score phase, sharded (pods × nodes).

    Mirrors the first half of ops/backend._mask_and_solve: returns
    (mask (P,N), feasible (P,N), static_scores (P,N)) with mask excluding
    capacity (the solver re-checks capacity live) and static_scores =
    host_scores + w_taint × taint score over the feasible set.
    """
    phase = _masks_scores_phase(mesh, strategy)
    return phase(alloc_q, used_q, used_nz_q, alloc_pods, used_pods, req_q,
                 req_nz_q, untol_f, untol_p, taint_f_mat, taint_p_mat,
                 static_mask, host_scores, jnp.float32(w_taint),
                 jnp.bool_(taint_filter_on))


def _masks_scores_phase(mesh: Mesh, strategy: str):
    """Jitted phase cached per (mesh, strategy) — pjit rejects kwargs when
    in_shardings is given, so the static strategy lives in the closure."""
    key = (mesh, strategy)
    fn = _PHASE_CACHE.get(key)
    if fn is not None:
        return fn
    pn = NamedSharding(mesh, P(PODS_AXIS, NODES_AXIS))
    n_r = NamedSharding(mesh, P(NODES_AXIS, None))
    n_ = NamedSharding(mesh, P(NODES_AXIS))
    p_r = NamedSharding(mesh, P(PODS_AXIS, None))

    @partial(jax.jit,
             in_shardings=(n_r, n_r, n_r, n_, n_, p_r, p_r, p_r, p_r,
                           n_r, n_r, pn, pn, None, None),
             out_shardings=(pn, pn, pn))
    def phase(alloc_q, used_q, used_nz_q, alloc_pods, used_pods, req_q,
              req_nz_q, untol_f, untol_p, taint_f_mat, taint_p_mat,
              static_mask, host_scores, w_taint, taint_filter_on):
        fit0 = kernels.fit_filter_mask(
            alloc_q, used_q, used_pods, alloc_pods, req_q)
        taint_ok = kernels.taint_filter_mask(taint_f_mat, untol_f)
        taint_ok = taint_ok | jnp.logical_not(taint_filter_on)
        mask = static_mask & taint_ok
        feasible = mask & fit0
        static_scores = host_scores + w_taint * kernels.taint_toleration_score(
            taint_p_mat, untol_p, feasible)
        return mask, feasible, static_scores

    _PHASE_CACHE[key] = phase
    return phase


# ---------------------------------------------------------------------------
# phase 2: sequential-equivalent solver (1-D nodes mesh)
# ---------------------------------------------------------------------------

def sharded_greedy_assign(mesh: Mesh, req_q, req_nz_q, free_q, free_pods,
                          used_nz_q, alloc_q, mask, static_scores,
                          fit_col_w, bal_col_mask, shape_u, shape_s,
                          w_fit, w_bal, strategy: str,
                          shortlist_k: int = 0, rows=None, exc=None,
                          row_req_q=None, row_req_nz_q=None,
                          wave_w: int = 0, pallas: str = "off",
                          block_w: int = 0):
    """Sequential-equivalent greedy with live re-scoring, node axis sharded.

    Per scan step: shard-local candidate (max score, min index among ties) →
    global winner via `pmax` then `pmin` over the nodes axis → winning shard
    debits capacity. Semantics match ops/solver.greedy_assign_rescoring
    exactly (ties → lowest global node index).

    shortlist_k > 0 prunes SHARD-LOCALLY before the cross-shard argmax:
    each shard prefilters its own top-K columns per pod (by shard-local
    chunk-start score) and re-scores only those plus its locally-debited
    nodes per step, with the same per-step exactness bound check and full
    local-row fallback as ops/solver's shortlist scans — so the local
    candidate entering the `pmax` is always the true shard maximum and the
    global winner is bit-identical. The per-step ICI reduction was already
    O(1) scalars; what shrinks is each shard's local reduce, N/devices →
    K/devices + touched. A shard narrower than K+1 columns keeps the full
    local scan (nothing to prune).

    block_w > 0 additionally routes each shard's PREFILTER through the
    two-pass block-sparse form (ops/solver.block_bound_prefilter) over
    its own column set: an O(C·B_local) bound scan gates which local
    columns the chunk-start pass touches, with the in-program full-width
    fallback whenever the exactness predicate fails — shard-local and
    collective-free, so the per-step pmax/pmin winner wire is untouched
    and assignments stay bit-identical at every shard count. A shard
    whose column count cannot satisfy the M+1 ≤ B_local shape guard
    keeps the full-width local prefilter (same clamp rule as the
    backend's tuner row).

    `pallas` is a mode `ops/pallas_kernel.resolve_mode` answered — the
    policy lives there, none here. "interpret" (the CPU test mode) or
    "compiled" (the real kernel, or its compile error) fuses each
    wave's shard-local (W, local_n) evaluation — plane gather,
    exception gate, capacity fit, live re-score, feasible masking —
    into one Pallas kernel per wave step
    (ops/pallas_kernel.wave_eval); "off" keeps the inline form.
    Everything that crosses the mesh is UNCHANGED: the W pmax/pmin
    winner rounds, the global-coordinate conflict OR-reduce, and the
    commit/replay cond stay in the shard_map body (SURVEY §5.8's ICI
    reduction contract), so assignments remain bit-identical at every
    shard count. The shortlist path keeps its W=1 scan (shortlist_k
    wins when both are set), as before.

    Class-dictionary planes (the r14 format): `mask`/`static_scores` may
    carry C CLASS rows instead of P pod rows — pass `rows` ((P,) pod →
    plane row), `row_req_q`/`row_req_nz_q` ((C,R) per-row request
    vectors, used by the shard-local prefilter so it too runs over C
    rows), and optionally `exc` ((P,) GLOBAL single-allowed-column
    exception, -1 = none). Defaults reproduce the per-pod form
    (rows = arange, row_req = req).

    wave_w > 1 runs the SPECULATIVE WAVEFRONT form of the same solver
    (the r18 scan — see ops/solver.py): W pods per scan step, each
    wave's prefix-distinct argmax resolved under the SAME per-step
    `pmax`/`pmin` shard reduction (W rounds per wave instead of one per
    pod), conflicts detected in GLOBAL node coordinates (each commit's
    owner shard re-scores it for later members; the (W,) conflict bits
    OR-reduce across the mesh so every shard takes the same
    fast-commit/serial-replay branch) — assignments bit-identical to the
    serial sharded scan at every W and every shard count. Composes with
    class planes and exceptions; the shortlist path keeps its W=1 scan
    (shortlist_k wins when both are set)."""
    n_shards = mesh.shape[NODES_AXIS]
    n_total = free_q.shape[0]
    assert n_total % n_shards == 0, (n_total, n_shards)
    local_n = n_total // n_shards
    k = min(shortlist_k, local_n - 1) if shortlist_k else 0
    run = _solver_fn(mesh, strategy, local_n, shortlist_k=max(k, 0),
                     wave_w=0 if k else max(0, wave_w),
                     pallas=pallas if not k and wave_w > 1 else "off",
                     block_w=_block_w_for(block_w, k, local_n))
    p = req_q.shape[0]
    if rows is None:
        rows = jnp.arange(p, dtype=jnp.int32)
    if exc is None:
        exc = jnp.full((p,), -1, dtype=jnp.int32)
    if row_req_q is None:
        row_req_q = req_q
    if row_req_nz_q is None:
        row_req_nz_q = req_nz_q
    return run(req_q, req_nz_q, jnp.asarray(rows), jnp.asarray(exc),
               jnp.asarray(row_req_q), jnp.asarray(row_req_nz_q),
               free_q, free_pods, used_nz_q, alloc_q,
               mask, static_scores, fit_col_w, bal_col_mask,
               jnp.asarray(shape_u), jnp.asarray(shape_s),
               jnp.float32(w_fit), jnp.float32(w_bal))


def _wave_body(mesh, axes, local_n, base, iota, strategy, wave_w,
               local_full, _reduce,
               req_q, req_nz_q, rows, exc, free_q, free_pods, used_nz,
               alloc_q, mask, static_sc, fit_col_w, bal_col_mask,
               shape_u, shape_s, w_fit, w_bal, pallas: str = "off"):
    """The wavefront wave-step body of the sharded solver (traced inside
    the shard_map `run`; see sharded_greedy_assign's wave_w contract).

    Per wave: ONE shard-local (W, local_n) evaluation against the carry,
    then W prefix-distinct global argmax rounds (the same `pmax`→`pmin`
    winner reduction the serial step runs once per pod, with earlier
    picks masked out on their owner shard), a conflict check in GLOBAL
    coordinates — each pick's owner shard re-scores it after its debit
    for every later member, and the (W,W) beats matrix OR-reduces over
    the mesh into replicated (W,) conflict bits — and a replicated-
    predicate cond: fast vectorized commit (owners scatter their picks'
    debits) or the serial replay (the one-pod step body, W times, exact).
    Speculative picks and the replay share the serial tie rule (lowest
    GLOBAL node index among max scorers), so assignments match the
    serial sharded scan bit-for-bit at every W and shard count."""
    from kubernetes_tpu.ops.solver import _wave_split

    p = req_q.shape[0]
    W = max(1, min(wave_w, p))
    ex = jnp.full((p,), -1, jnp.int32) if exc is None else exc
    (req_w, req_nz_w, rows_w, ex_w), real_w, _ = _wave_split(
        W, (req_q, req_nz_q, rows, ex))
    w_iota = jnp.arange(W, dtype=jnp.int32)
    def wave_step(carry, inp):
        free_q, free_pods, used_nz = carry
        req, req_nz, row, e, real = inp
        el = e - base                                   # local exc coords
        if pallas != "off":
            # Fused shard-local evaluation: same op sequence, one
            # kernel — the inline form below is the bit-identical
            # reference (tests/test_pallas_solver.py).
            masked, m = pallas_kernel.wave_eval(
                mask, static_sc, alloc_q, free_q, free_pods, used_nz,
                req, req_nz, row, e, el, real, fit_col_w, bal_col_mask,
                shape_u, shape_s, w_fit, w_bal, strategy,
                interpret=pallas == "interpret")
        else:
            m = mask[row] \
                & ((e < 0)[:, None] | (iota[None, :] == el[:, None])) \
                & real[:, None]                         # (W, local_n)
            fits = m & jnp.all(req[:, None, :] <= free_q[None, :, :],
                               axis=-1) & (free_pods >= 1)[None, :]
            sc = static_sc[row]
            sc = sc + w_fit * kernels.fit_score(
                alloc_q, used_nz, req_nz, fit_col_w, strategy, shape_u,
                shape_s)
            sc = sc + w_bal * kernels.balanced_allocation_score(
                alloc_q, used_nz, req_nz, bal_col_mask)
            masked = jnp.where(fits, sc, -jnp.inf)
        # Prefix-distinct GLOBAL picks: per member, one local max with
        # earlier picks masked out (owner shard), then the serial step's
        # pmax/pmin winner reduction.
        bs, ys = [], []
        for w in range(W):
            rv = masked[w]
            for yp in ys:
                rv = jnp.where(iota + base == yp, -jnp.inf, rv)
            lbest = jnp.max(rv)
            lidx = jnp.min(jnp.where(rv == lbest, iota, local_n))
            gbest = _reduce(lbest, lax.pmax)
            gcand = jnp.where((lidx < local_n) & (lbest >= gbest),
                              lidx + base, _INT_MAX)
            gidx = _reduce(gcand, lax.pmin)
            ys.append(jnp.where(jnp.isfinite(gbest), gidx, _INT_MAX))
            bs.append(gbest)
        b = jnp.stack(bs)
        y = jnp.stack(ys)                               # global ids
        hit = y < _INT_MAX
        li = y - base
        own = (li >= 0) & (li < local_n)                # pick owner bits
        safe = jnp.clip(li, 0, local_n - 1)
        # Conflicts in global coordinates: the owner of each pick y_j
        # re-scores it after member j's debit for every later member w;
        # non-owners contribute False and the bits OR-reduce replicated.
        fr_j = free_q[safe] - req                       # (W,R) owner-valid
        fp_j = free_pods[safe] - 1
        unz_j = used_nz[safe] + req_nz
        al_j = alloc_q[safe]
        upd = static_sc[row[:, None], safe[None, :]] \
            + w_fit * kernels.fit_score(
                al_j, unz_j, req_nz, fit_col_w, strategy, shape_u, shape_s) \
            + w_bal * kernels.balanced_allocation_score(
                al_j, unz_j, req_nz, bal_col_mask)      # (W,W)
        cap = jnp.all(req[:, None, :] <= fr_j[None, :, :], axis=-1)
        feas = m[:, safe] & cap & (fp_j >= 1)[None, :] \
            & (hit & own)[None, :]
        beats = feas & ((upd > b[:, None])
                        | ((upd == b[:, None]) & (y[None, :] < y[:, None])))
        tri = w_iota[None, :] < w_iota[:, None]
        conflict_local = jnp.any(beats & tri, axis=1).astype(jnp.int32)
        conflict = _reduce(conflict_local, lax.pmax) > 0

        def fast(st):
            fq, fp, unz = st
            inb = own & hit
            fq = fq.at[safe].add(
                jnp.where(inb[:, None], -req, 0).astype(fq.dtype))
            fp = fp.at[safe].add(jnp.where(inb, -1, 0).astype(fp.dtype))
            unz = unz.at[safe].add(
                jnp.where(inb[:, None], req_nz, 0).astype(unz.dtype))
            return (fq, fp, unz), \
                jnp.where(hit, y, jnp.int32(-1)).astype(jnp.int32)

        def slow(st):
            fq, fp, unz = st

            def body(w, s):
                fq, fp, unz, out = s
                m_w = mask[row[w]] \
                    & ((e[w] < 0) | (iota == el[w])) & real[w]
                lbest, lidx = local_full(req[w], req_nz[w], m_w,
                                         static_sc[row[w]], fq, fp, unz)
                gbest = _reduce(lbest, lax.pmax)
                gcand = jnp.where((lidx < local_n) & (lbest >= gbest),
                                  lidx + base, _INT_MAX)
                gidx = _reduce(gcand, lax.pmin)
                chosen = jnp.where(jnp.isfinite(gbest), gidx,
                                   jnp.int32(-1))
                lw = chosen - base
                inb = (lw >= 0) & (lw < local_n)
                sf = jnp.clip(lw, 0, local_n - 1)
                fq = fq.at[sf].add(
                    jnp.where(inb, -req[w], 0).astype(fq.dtype))
                fp = fp.at[sf].add(jnp.where(inb, -1, 0).astype(fp.dtype))
                unz = unz.at[sf].add(
                    jnp.where(inb, req_nz[w], 0).astype(unz.dtype))
                return (fq, fp, unz, out.at[w].set(chosen))

            fq, fp, unz, out = lax.fori_loop(
                0, W, body, (fq, fp, unz, jnp.full((W,), -1, jnp.int32)))
            return (fq, fp, unz), out

        return lax.cond(jnp.any(conflict), slow, fast,
                        (free_q, free_pods, used_nz))

    xs = (req_w, req_nz_w, rows_w, ex_w, real_w)
    _, out = lax.scan(wave_step, (free_q, free_pods, used_nz), xs)
    return out.reshape(-1)[:p]


def _solver_fn(mesh: Mesh, strategy: str, local_n: int,
               axes: tuple[str, ...] = (NODES_AXIS,),
               shortlist_k: int = 0, wave_w: int = 0,
               pallas: str = "off", block_w: int = 0):
    """One solver body for every mesh shape: the node dimension shards over
    `axes` (flattened, first axis major). Reductions run innermost-axis
    first, so a (slice, nodes) pair reduces slice-locally over ICI before
    ONE scalar per slice crosses DCN — the hierarchical argmax of SURVEY
    §5.7 falls out of the axis order. wave_w > 1 compiles the wavefront
    wave-step body instead of the one-pod step (mutually exclusive with
    shortlist_k; the caller routes)."""
    key = (mesh, strategy, local_n, axes, shortlist_k, wave_w, pallas,
           block_w)
    fn = _SOLVER_CACHE.get(key)
    if fn is not None:
        return fn

    spec_nr = P(axes, None)
    spec_n = P(axes)
    spec_pn = P(None, axes)
    rep = P()

    def _reduce(val, op):
        for a in reversed(axes):  # innermost (ICI) first, outermost last
            val = op(val, a)
        return val

    @jax.jit
    @partial(shard_map, mesh=mesh,
             in_specs=(rep, rep, rep, rep, rep, rep,
                       spec_nr, spec_n, spec_nr, spec_nr,
                       spec_pn, spec_pn, rep, rep, rep, rep, rep, rep),
             out_specs=rep, check_vma=False)
    def run(req_q, req_nz_q, rows, exc, row_req_q, row_req_nz_q,
            free_q, free_pods, used_nz, alloc_q,
            mask, static_sc, fit_col_w, bal_col_mask, shape_u, shape_s,
            w_fit, w_bal):
        shard = jnp.int32(0)
        for a in axes:
            # mesh.shape is static — lax.axis_size only exists on newer jax.
            shard = shard * mesh.shape[a] + lax.axis_index(a)
        base = (shard * local_n).astype(jnp.int32)
        iota = jnp.arange(local_n, dtype=jnp.int32)
        p_pods = req_q.shape[0]

        def local_full(req, req_nz, m, sc_static, free_q, free_pods,
                       used_nz):
            """Exact local (best score, local argmin-index) over the whole
            shard — the unpruned per-step body and the fallback branch."""
            fits = m & jnp.all(req[None, :] <= free_q, axis=1) \
                & (free_pods >= 1)
            sc = sc_static
            sc = sc + w_fit * kernels.fit_score(
                alloc_q, used_nz, req_nz[None, :], fit_col_w, strategy,
                shape_u, shape_s)[0]
            sc = sc + w_bal * kernels.balanced_allocation_score(
                alloc_q, used_nz, req_nz[None, :], bal_col_mask)[0]
            masked = jnp.where(fits, sc, -jnp.inf)
            lbest = jnp.max(masked)
            lidx = jnp.min(jnp.where(masked == lbest, iota, local_n))
            return lbest, lidx.astype(jnp.int32)

        if wave_w > 1:
            return _wave_body(
                mesh, axes, local_n, base, iota, strategy, wave_w,
                local_full, _reduce,
                req_q, req_nz_q, rows, exc, free_q, free_pods, used_nz,
                alloc_q, mask, static_sc, fit_col_w, bal_col_mask,
                shape_u, shape_s, w_fit, w_bal, pallas=pallas)

        if shortlist_k:
            # Shard-local prefilter: chunk-start scores over MY columns,
            # per-PLANE-ROW top-K + the (K+1)-th value as the local
            # threshold — C class rows when the caller ships class
            # planes, P pod rows in the identity form. block_w > 0
            # routes the two-pass block-sparse form over this shard's
            # columns: the bound scan, gather, and the in-program
            # full-width fallback are all shard-LOCAL (no collective —
            # shards may even take different cond branches), and local
            # padding columns are handled by feasibility alone
            # (n_real = local_n: a looser bound for a block holding
            # global pad columns can only cost pruning, never
            # exactness). Local-index tie rules line up exactly because
            # the gather preserves ascending local column order.
            fits0 = jnp.all(row_req_q[:, None, :] <= free_q[None, :, :],
                            axis=-1) & (free_pods >= 1)[None, :]
            if block_w:
                sc0, sl_cand, sl_t, _, _ = solver.block_bound_prefilter(
                    alloc_q, used_nz, row_req_nz_q, static_sc,
                    mask & fits0, fit_col_w, bal_col_mask, shape_u,
                    shape_s, w_fit, w_bal, strategy,
                    jnp.int32(local_n), shortlist_k, block_w)
            else:
                sc0 = kernels.chunk_start_scores(
                    alloc_q, used_nz, row_req_nz_q, static_sc, fit_col_w,
                    bal_col_mask, shape_u, shape_s, w_fit, w_bal,
                    strategy)
                vals, cand0 = lax.top_k(
                    jnp.where(mask & fits0, sc0, -jnp.inf),
                    shortlist_k + 1)
                sl_cand = cand0[:, :shortlist_k].astype(jnp.int32)
                sl_t = vals[:, shortlist_k]

        def step(carry, inp):
            if shortlist_k:
                free_q, free_pods, used_nz, touched, tidx, kstep = carry
                req, req_nz, row, e = inp
                el = e - base  # exception column in LOCAL coordinates
                cand = sl_cand[row]
                t = sl_t[row]
                cset = jnp.concatenate([cand, tidx])
                valid = cset < local_n
                ci = jnp.where(valid, cset, 0)
                # (row, ci) element gathers off the closed-over local
                # planes — an (local_n,)-wide xs row per step would put
                # O(local_n) traffic back into the pruned scan.
                live = static_sc[row, ci]
                live = live + w_fit * kernels.fit_score(
                    alloc_q[ci], used_nz[ci], req_nz[None, :], fit_col_w,
                    strategy, shape_u, shape_s)[0]
                live = live + w_bal * kernels.balanced_allocation_score(
                    alloc_q[ci], used_nz[ci], req_nz[None, :],
                    bal_col_mask)[0]
                live = jnp.where(touched[ci], live, sc0[row, ci])
                fits = mask[row, ci] & valid \
                    & jnp.all(req[None, :] <= free_q[ci], axis=1) \
                    & (free_pods[ci] >= 1) \
                    & ((e < 0) | (ci == el))
                masked = jnp.where(fits, live, -jnp.inf)
                sbest = jnp.max(masked)
                any_l = sbest > -jnp.inf
                sidx = jnp.min(jnp.where(masked == sbest, ci, local_n)
                               ).astype(jnp.int32)
                w_t = touched[jnp.minimum(sidx, local_n - 1)]
                trusted = jnp.where(
                    any_l,
                    (sbest > t) | ((sbest == t) & jnp.logical_not(w_t)),
                    t == -jnp.inf)

                def fb(_):
                    m = mask[row] & ((e < 0) | (iota == el))
                    return local_full(req, req_nz, m, static_sc[row],
                                      free_q, free_pods, used_nz)

                lbest, lidx = lax.cond(
                    trusted,
                    lambda _: (sbest,
                               jnp.where(any_l, sidx, jnp.int32(local_n))),
                    fb, None)
            else:
                free_q, free_pods, used_nz = carry
                req, req_nz, row, e = inp
                m = mask[row] & ((e < 0) | (iota == (e - base)))
                lbest, lidx = local_full(req, req_nz, m, static_sc[row],
                                         free_q, free_pods, used_nz)
            gbest = _reduce(lbest, lax.pmax)
            # Tie-break: lowest global index among shards holding gbest.
            gcand = jnp.where((lidx < local_n) & (lbest >= gbest),
                              lidx + base, _INT_MAX)
            gidx = _reduce(gcand, lax.pmin)
            chosen = jnp.where(jnp.isfinite(gbest), gidx, jnp.int32(-1))
            li = chosen - base
            inb = (li >= 0) & (li < local_n)
            safe = jnp.clip(li, 0, local_n - 1)
            free_q = free_q.at[safe].add(
                jnp.where(inb, -req, 0).astype(free_q.dtype))
            free_pods = free_pods.at[safe].add(
                jnp.where(inb, -1, 0).astype(free_pods.dtype))
            used_nz = used_nz.at[safe].add(
                jnp.where(inb, req_nz, 0).astype(used_nz.dtype))
            if shortlist_k:
                touched = touched.at[safe].set(touched[safe] | inb)
                tidx = tidx.at[kstep].set(jnp.where(inb, li, local_n))
                return (free_q, free_pods, used_nz, touched, tidx,
                        kstep + 1), chosen
            return (free_q, free_pods, used_nz), chosen

        if shortlist_k:
            carry0 = (free_q, free_pods, used_nz,
                      jnp.zeros((local_n,), jnp.bool_),
                      jnp.full((p_pods,), local_n, jnp.int32),
                      jnp.int32(0))
        else:
            carry0 = (free_q, free_pods, used_nz)
        _, assign = lax.scan(step, carry0, (req_q, req_nz_q, rows, exc))
        return assign

    _SOLVER_CACHE[key] = run
    return run


# ---------------------------------------------------------------------------
# phase 2a: Sinkhorn transport plan (optimal solve mode, nodes axis sharded)
# ---------------------------------------------------------------------------

_SINKHORN_CACHE: dict = {}


def sharded_sinkhorn_plan(mesh: Mesh, feasible, cost, row_counts, col_cap,
                          iters, temp,
                          axes: tuple[str, ...] = (NODES_AXIS,)):
    """ops/solver.sinkhorn_plan with the NODE (column) axis sharded.

    The (C,N) class planes keep C small and replicated; each shard owns
    an N/devices column block of feasible/cost and its slice of the
    column capacities. Per iteration the only cross-shard traffic is the
    row marginal `K @ v` — a (C,) psum over the mesh (innermost axis
    first, the SURVEY §5.7 hierarchical-reduction order) — plus one
    (C,) pmax up front for the row-max shift; the column update is
    purely shard-local because `u` is replicated. Same annealing
    schedule, same inequality column update, same sanitized log-plan
    output as the single-device form (tests pin allclose parity at
    {1,4,8} shards)."""
    fn = _sinkhorn_fn(mesh, axes)
    return fn(feasible, cost, row_counts, col_cap,
              jnp.int32(iters), jnp.float32(temp))


def _sinkhorn_fn(mesh: Mesh, axes: tuple[str, ...]):
    key = (mesh, axes)
    fn = _SINKHORN_CACHE.get(key)
    if fn is not None:
        return fn

    spec_cn = P(None, axes)
    spec_n = P(axes)
    rep = P()

    def _reduce(val, op):
        for a in reversed(axes):  # innermost (ICI) first, outermost last
            val = op(val, a)
        return val

    @jax.jit
    @partial(shard_map, mesh=mesh,
             in_specs=(spec_cn, spec_cn, rep, spec_n, rep, rep),
             out_specs=(spec_cn, spec_cn), check_vma=False)
    def sink_run(feasible, cost, row_counts, col_cap, iters, temp):
        from kubernetes_tpu.ops.solver import SINKHORN_STAGES

        a = row_counts.astype(jnp.float32)
        b = jnp.maximum(col_cap.astype(jnp.float32), 0.0)
        eps = jnp.float32(1e-12)
        n_iters = jnp.maximum(iters, 1)
        stages = jnp.int32(SINKHORN_STAGES)
        kmask = feasible.astype(jnp.float32)
        lrmax = jnp.max(jnp.where(feasible, cost.astype(jnp.float32),
                                  -jnp.inf), axis=1, keepdims=True)
        rmax = _reduce(lrmax, lax.pmax)
        sc = jnp.where(feasible, cost.astype(jnp.float32) - rmax, 0.0)

        def kernel(stage):
            t = temp * jnp.exp2((stages - 1 - stage).astype(jnp.float32))
            return kmask * jnp.exp(sc / jnp.maximum(t, eps))

        def step(i, uv):
            u, v = uv
            k = kernel(jnp.minimum((stages * i) // n_iters, stages - 1))
            row = _reduce(k @ v, lax.psum)      # (C,) global row marginal
            u = a / jnp.maximum(row, eps)
            col = u @ k                          # shard-local: u replicated
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

    _SINKHORN_CACHE[key] = sink_run
    return sink_run


# ---------------------------------------------------------------------------
# resident-plane row scatter (the serving tier's device-side delta)
# ---------------------------------------------------------------------------

_SCATTER_CACHE: dict = {}


def resident_row_scatter(mesh: Mesh | None, sharding=None):
    """Jitted `pack.at[rows].set(vals)` for the serving tier's resident
    used-state planes (serving/resident.py): the device-side twin of the
    r13 per-shard delta requantization. Rows/vals are tiny (the cache's
    dirty set — O(assumed pods) per cycle), so under a mesh they ride
    replicated while the (N, 2R+1) pack stays sharded over the nodes
    axis: `out_shardings` pins the result's sharding so the resident
    array never silently de-shards across refreshes (a gathered pack
    would re-pay the full-upload cost the scatter exists to avoid). On
    a single device (mesh=None) it is a plain jitted scatter.

    Cached per (mesh, sharding) like the solver bodies."""
    key = (mesh, sharding)
    fn = _SCATTER_CACHE.get(key)
    if fn is not None:
        return fn

    def body(pack, rows, vals):
        return pack.at[rows].set(vals)

    if mesh is not None and sharding is not None:
        fn = jax.jit(body, out_shardings=sharding)
    else:
        fn = jax.jit(body)
    _SCATTER_CACHE[key] = fn
    return fn


# ---------------------------------------------------------------------------
# phase 2b: multi-slice solver (2-D slice × nodes mesh — config #5)
# ---------------------------------------------------------------------------

def sharded_greedy_assign_multislice(mesh: Mesh, req_q, req_nz_q, free_q,
                                     free_pods, used_nz_q, alloc_q, mask,
                                     static_scores, fit_col_w, bal_col_mask,
                                     shape_u, shape_s, w_fit, w_bal,
                                     strategy: str, shortlist_k: int = 0,
                                     rows=None, exc=None,
                                     row_req_q=None, row_req_nz_q=None,
                                     wave_w: int = 0,
                                     pallas: str = "off",
                                     block_w: int = 0):
    """Sequential-equivalent greedy over a (slice × nodes) mesh: the same
    solver body as `sharded_greedy_assign`, with the node dimension sharded
    over BOTH axes and the per-step argmax reduced hierarchically —
    slice-local `pmax` over ICI, then ONE scalar per slice across DCN, so
    cross-slice traffic is O(1) per pod regardless of node count (the 50k
    config #5 enabler). Tie-break matches the single-device solver.
    wave_w as in sharded_greedy_assign (the wave reductions reduce
    hierarchically through the same axis order)."""
    s_shards = mesh.shape[SLICE_AXIS]
    n_shards = mesh.shape[NODES_AXIS]
    n_total = free_q.shape[0]
    shards = s_shards * n_shards
    assert n_total % shards == 0, (n_total, shards)
    local_n = n_total // shards
    k = min(shortlist_k, local_n - 1) if shortlist_k else 0
    run = _solver_fn(mesh, strategy, local_n,
                     axes=(SLICE_AXIS, NODES_AXIS), shortlist_k=max(k, 0),
                     wave_w=0 if k else max(0, wave_w),
                     pallas=pallas if not k and wave_w > 1 else "off",
                     block_w=_block_w_for(block_w, k, local_n))
    p = req_q.shape[0]
    if rows is None:
        rows = jnp.arange(p, dtype=jnp.int32)
    if exc is None:
        exc = jnp.full((p,), -1, dtype=jnp.int32)
    if row_req_q is None:
        row_req_q = req_q
    if row_req_nz_q is None:
        row_req_nz_q = req_nz_q
    return run(req_q, req_nz_q, jnp.asarray(rows), jnp.asarray(exc),
               jnp.asarray(row_req_q), jnp.asarray(row_req_nz_q),
               free_q, free_pods, used_nz_q, alloc_q,
               mask, static_scores, fit_col_w, bal_col_mask,
               jnp.asarray(shape_u), jnp.asarray(shape_s),
               jnp.float32(w_fit), jnp.float32(w_bal))
