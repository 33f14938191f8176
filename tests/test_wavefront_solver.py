"""Randomized differential parity for the speculative wavefront solve.

The contract under test: every `*_wave` scan in ops/solver.py produces
assignments BIT-IDENTICAL to its W=1 counterpart at every wave width —
tight-capacity conflict storms (speculation must replay, exactly),
packing strategies whose scores RISE on debit (the non-monotone hazard
the pairwise re-score exists for), spread constraints with contested
domains (the structural non-monotonicity rule), the shortlist∩wavefront
composition, sharded meshes at {1, 4, 8}, and the W ∈ {1, 2, 8, P}
extremes including W > P. The tier-1 activation/kill-switch/tuner pins
live in tests/test_wavefront_smoke.py.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kubernetes_tpu.ops import kernels, solver

WIDTHS = (1, 2, 8)


def _mesh_sizes():
    """The mesh widths this machine can build (8 virtual CPU devices
    under conftest; one or four real ones on the chip)."""
    return [s for s in (1, 4, 8) if s <= len(jax.devices())]


def _problem(rng, n, p, r, tight=False, strategy="LeastAllocated",
             classes=None):
    """Random solver arg dict; tight=True makes capacity contested so
    speculative picks collide with earlier debits (the replay path)."""
    if tight:
        alloc_q = rng.integers(2, 6, size=(n, r)).astype(np.int32) * 1000
        req_q = rng.integers(500, 2500, size=(p, r)).astype(np.int32)
        free_pods = rng.integers(1, 3, size=(n,)).astype(np.int32)
    else:
        alloc_q = rng.integers(20, 60, size=(n, r)).astype(np.int32) * 1000
        req_q = rng.integers(100, 3000, size=(p, r)).astype(np.int32)
        free_pods = rng.integers(2, 8, size=(n,)).astype(np.int32)
    used_q = (alloc_q * rng.uniform(0, 0.5, size=(n, r))).astype(np.int32)
    if classes:
        rows = rng.integers(0, classes, size=(p,)).astype(np.int32)
        # Pods of one class share request rows (the class-key contract).
        class_req = rng.integers(100, 3000, size=(classes, r)).astype(np.int32)
        req_q = class_req[rows]
        mask = rng.random((classes, n)) > 0.15
        scores = rng.uniform(0, 4, size=(classes, n)).astype(np.float32)
    else:
        rows = None
        mask = rng.random((p, n)) > 0.15
        scores = rng.uniform(0, 4, size=(p, n)).astype(np.float32)
    args = dict(
        req_q=jnp.asarray(req_q), req_nz_q=jnp.asarray(req_q),
        free_q=jnp.asarray(alloc_q - used_q),
        free_pods=jnp.asarray(free_pods),
        used_nz_q=jnp.asarray(used_q), alloc_q=jnp.asarray(alloc_q),
        mask=jnp.asarray(mask), static_scores=jnp.asarray(scores),
        fit_col_w=jnp.ones((r,), jnp.float32),
        bal_col_mask=jnp.ones((r,), np.bool_),
        shape_u=jnp.asarray([0.0, 100.0], jnp.float32),
        shape_s=jnp.asarray([0.0, 10.0], jnp.float32),
        w_fit=jnp.float32(1.0), w_bal=jnp.float32(1.0))
    if rows is not None:
        args["rows"] = jnp.asarray(rows)
    return args, (np.asarray(mask), np.asarray(scores))


class TestRescoringWaveParity:
    @pytest.mark.parametrize("strategy",
                             ["LeastAllocated", "MostAllocated",
                              "RequestedToCapacityRatio"])
    def test_conflict_storm_bit_identity(self, strategy):
        """Tight capacity + every strategy (incl. the ones whose score
        RISES on debit): assignments equal the serial scan at every W."""
        for seed in range(4):
            rng = np.random.default_rng(seed)
            args, _ = _problem(rng, n=24, p=31, r=2, tight=True)
            ref = np.asarray(solver.greedy_assign_rescoring(
                strategy=strategy, **args))
            for w in WIDTHS + (31, 64):
                a, com, rep = solver.greedy_assign_rescoring_wave(
                    strategy=strategy, wave_w=w, **args)
                np.testing.assert_array_equal(np.asarray(a), ref,
                                              err_msg=f"W={w} {strategy}")
                assert int(com) + int(rep) == 31

    def test_class_planes_and_exceptions(self):
        """Class-row indirection + pinned-column exceptions ride the
        wave exactly like the serial scan."""
        for seed in range(3):
            rng = np.random.default_rng(100 + seed)
            args, _ = _problem(rng, n=40, p=26, r=3, classes=4)
            exc = np.full((26,), -1, np.int32)
            exc[rng.integers(0, 26, size=5)] = \
                rng.integers(0, 40, size=5).astype(np.int32)
            args["exc"] = jnp.asarray(exc)
            ref = np.asarray(solver.greedy_assign_rescoring(
                strategy="LeastAllocated", **args))
            for w in WIDTHS:
                a, _, _ = solver.greedy_assign_rescoring_wave(
                    strategy="LeastAllocated", wave_w=w, **args)
                np.testing.assert_array_equal(np.asarray(a), ref)

    def test_uniform_template_commits_speculatively(self):
        """The template regime (identical pods, uniform nodes — the
        bench presets' shape): prefix-distinct speculation must commit
        without replays, or the wavefront buys nothing where it matters."""
        n, p, r = 256, 64, 2
        args = dict(
            req_q=jnp.asarray(np.full((p, r), 500, np.int32)),
            req_nz_q=jnp.asarray(np.full((p, r), 500, np.int32)),
            free_q=jnp.asarray(np.full((n, r), 8000, np.int32)),
            free_pods=jnp.asarray(np.full((n,), 110, np.int32)),
            used_nz_q=jnp.asarray(np.zeros((n, r), np.int32)),
            alloc_q=jnp.asarray(np.full((n, r), 8000, np.int32)),
            mask=jnp.asarray(np.ones((1, n), np.bool_)),
            static_scores=jnp.asarray(np.zeros((1, n), np.float32)),
            fit_col_w=jnp.ones((r,), jnp.float32),
            bal_col_mask=jnp.ones((r,), np.bool_),
            shape_u=jnp.zeros((2,), jnp.float32),
            shape_s=jnp.zeros((2,), jnp.float32),
            w_fit=jnp.float32(1.0), w_bal=jnp.float32(1.0),
            rows=jnp.asarray(np.zeros((p,), np.int32)))
        ref = np.asarray(solver.greedy_assign_rescoring(
            strategy="LeastAllocated", **args))
        a, com, rep = solver.greedy_assign_rescoring_wave(
            strategy="LeastAllocated", wave_w=8, **args)
        np.testing.assert_array_equal(np.asarray(a), ref)
        assert int(rep) == 0 and int(com) == p


class TestMultistartWaveParity:
    def test_permuted_orders_and_gangs(self):
        for seed in range(3):
            rng = np.random.default_rng(200 + seed)
            p = 24
            args, _ = _problem(rng, n=48, p=p, r=2, tight=(seed == 0))
            perms, gang, gr = _multi_orders(rng, p, 4).values()
            # One gang of 5 with an unreachable quota: all-or-nothing
            # must drop its partial placements identically.
            gang = np.asarray(gang).copy()
            gang[:5, 0] = 1.0
            grq = np.asarray(gr).copy()
            grq[0] = 5.0
            ref = np.asarray(solver.multistart_greedy_assign(
                strategy="LeastAllocated", perms=perms,
                gang_onehot=jnp.asarray(gang),
                gang_required=jnp.asarray(grq), **args))
            for w in WIDTHS:
                a, com, rep = solver.multistart_greedy_assign_wave(
                    strategy="LeastAllocated", wave_w=w, perms=perms,
                    gang_onehot=jnp.asarray(gang),
                    gang_required=jnp.asarray(grq), **args)
                np.testing.assert_array_equal(np.asarray(a), ref)
                # Poisoned chunks rerun the W=1 multistart whole; either
                # way accounting covers the chunk once.
                assert int(com) + int(rep) == p


def _shortlist_tables(args, masks, k, strategy, per_pod=False, exc=None):
    """Shortlist state for one problem, as `_mask_solve_update` builds
    it: one shortlist row per plane row (S = C, sl_class = rows — pods
    of a class share requests, so they share chunk-start scores), or
    with per_pod the identity form S = P (a row per pod, whatever the
    planes). Returns (wave, serial): the class tables the wave entries
    take, and their per-pod expansion plus sc0 for the W=1 entries."""
    mask_np, scores_np = masks
    free_q = np.asarray(args["free_q"])
    req = np.asarray(args["req_q"])
    p = req.shape[0]
    rows = np.asarray(args["rows"]) if "rows" in args \
        else np.arange(p, dtype=np.int32)
    if per_pod:
        sl_class = np.arange(p, dtype=np.int32)
        req_s, mask_s, stat_s = req, mask_np[rows], scores_np[rows]
    else:
        sl_class = rows
        req_s = np.zeros((mask_np.shape[0], req.shape[1]), np.int32)
        req_s[rows] = req
        mask_s, stat_s = mask_np, scores_np
    sc0 = kernels.chunk_start_scores(
        args["alloc_q"], args["used_nz_q"], jnp.asarray(req_s),
        jnp.asarray(stat_s), args["fit_col_w"], args["bal_col_mask"],
        args["shape_u"], args["shape_s"], args["w_fit"], args["w_bal"],
        strategy)
    feas0 = jnp.asarray(
        mask_s
        & np.all(req_s[:, None, :] <= free_q[None, :, :], axis=-1)
        & (np.asarray(args["free_pods"]) >= 1)[None, :])
    cand, thresh = solver.shortlist_prefilter(feas0, sc0, k)
    val = jnp.where(jnp.take_along_axis(feas0, cand, axis=1),
                    jnp.take_along_axis(sc0, cand, axis=1), solver.NEG_INF)
    hn = mask_np[rows].any(axis=1)
    if exc is not None:
        # the backend's narrowing: a pinned pod's only possible node
        pin = np.clip(exc, 0, mask_np.shape[1] - 1)
        hn = np.where(exc >= 0, mask_np[rows, pin], hn)
    hn = jnp.asarray(hn)
    cls = jnp.asarray(sl_class)
    wave = dict(sl_class=cls, sl_cand=cand, sl_val=val, sl_thresh=thresh,
                has_node=hn)
    serial = dict(sc0=sc0, sl_class=cls, sl_cand=cand[cls],
                  sl_thresh=thresh[cls], has_node=hn)
    return wave, serial


def _multi_orders(rng, p, k):
    perms = np.tile(np.arange(p, dtype=np.int32), (k, 1))
    for i in range(1, k):
        perms[i] = rng.permutation(p).astype(np.int32)
    return dict(perms=jnp.asarray(perms),
                gang_onehot=jnp.zeros((p, 16), jnp.float32),
                gang_required=jnp.zeros((16,), jnp.float32))


class TestShortlistWaveParity:
    @pytest.mark.parametrize("strategy",
                             ["LeastAllocated", "MostAllocated"])
    def test_shortlist_wave_bit_identity(self, strategy):
        """shortlist∩wavefront: the pick must clear BOTH the bound check
        and the pairwise wave check; either failure replays exactly."""
        for seed in range(4):
            rng = np.random.default_rng(300 + seed)
            args, masks = _problem(rng, n=64, p=19, r=2,
                                   tight=(seed % 2 == 0))
            # the identity form: a shortlist row per pod
            sl, _ = _shortlist_tables(args, masks, k=6, strategy=strategy,
                                      per_pod=True)
            ref = np.asarray(solver.greedy_assign_rescoring(
                strategy=strategy, **args))
            for w in WIDTHS + (19,):
                a, nfall, com, rep = \
                    solver.greedy_assign_rescoring_shortlist_wave(
                        strategy=strategy, wave_w=w, **sl, **args)
                np.testing.assert_array_equal(
                    np.asarray(a), ref, err_msg=f"W={w} {strategy}")
                assert int(com) + int(rep) == 19

    def test_multistart_shortlist_wave(self):
        for seed in range(3):
            rng = np.random.default_rng(400 + seed)
            p = 16
            args, masks = _problem(rng, n=96, p=p, r=2)
            sl, sl1 = _shortlist_tables(args, masks, k=5,
                                        strategy="LeastAllocated",
                                        per_pod=True)
            multi = _multi_orders(rng, p, 3)
            ref, _ = solver.multistart_greedy_assign_shortlist(
                strategy="LeastAllocated", **multi, **sl1, **args)
            for w in WIDTHS:
                a, _, com, rep = \
                    solver.multistart_greedy_assign_shortlist_wave(
                        strategy="LeastAllocated", wave_w=w, **multi,
                        **sl, **args)
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(ref))
                assert int(com) + int(rep) == p

    @pytest.mark.parametrize("strategy",
                             ["LeastAllocated", "MostAllocated"])
    @pytest.mark.parametrize("classes", [1, 2, 5, None])
    def test_class_tables_bit_identity(self, classes, strategy):
        """Shared class rows (S = C < P: the members of a wave read
        their class's shortlist row and plane row) and the per-pod
        identity form (S = C = P: every lookup per member) against the
        full-width serial scan."""
        for seed in range(3):
            rng = np.random.default_rng(900 + seed)
            p = 37
            args, masks = _problem(rng, n=80, p=p, r=2,
                                   tight=(seed == 0), classes=classes)
            sl, _ = _shortlist_tables(args, masks, k=9, strategy=strategy)
            assert sl["sl_cand"].shape[0] == (classes or p)
            ref = np.asarray(solver.greedy_assign_rescoring(
                strategy=strategy, **args))
            for w in (4, 16):
                a, _, com, rep = \
                    solver.greedy_assign_rescoring_shortlist_wave(
                        strategy=strategy, wave_w=w, **sl, **args)
                np.testing.assert_array_equal(
                    np.asarray(a), ref,
                    err_msg=f"S={classes} W={w} seed={seed}")
                assert int(com) + int(rep) == p

    @pytest.mark.parametrize("wave_w", [2, 8])
    def test_touched_shortlist_nodes_repicked(self, wave_w):
        """MostAllocated on tight, uniform-request capacity: a node's
        score RISES when it is debited, so shortlist nodes committed in
        an early wave are picked again in later waves. Their shortlist
        slot must read -inf from then on (its chunk-start value is
        stale) while `tidx` carries the live value."""
        n, p, r, k = 48, 24, 2, 6
        rng = np.random.default_rng(77)
        args, masks = _problem(rng, n=n, p=p, r=r, classes=2)
        small = np.full((p, r), 600, np.int32)
        args["req_q"] = args["req_nz_q"] = jnp.asarray(small)
        alloc = np.asarray(args["alloc_q"])
        args["used_nz_q"] = jnp.asarray(np.zeros_like(alloc))
        args["free_q"] = jnp.asarray(alloc)
        # five slots a node: the piles straddle wave boundaries
        args["free_pods"] = jnp.asarray(np.full((n,), 5, np.int32))
        sl, _ = _shortlist_tables(args, masks, k=k,
                                  strategy="MostAllocated")
        ref = np.asarray(solver.greedy_assign_rescoring(
            strategy="MostAllocated", **args))
        a, nfall, com, rep = solver.greedy_assign_rescoring_shortlist_wave(
            strategy="MostAllocated", wave_w=wave_w, **sl, **args)
        np.testing.assert_array_equal(np.asarray(a), ref)
        # not vacuous: some shortlist node of the pod's class was
        # committed in one wave and picked again in a later one
        cand = np.asarray(sl["sl_cand"])
        cls = np.asarray(sl["sl_class"])
        first_wave = {}
        repicked = 0
        for i, node in enumerate(ref):
            if node < 0:
                continue
            wv = i // wave_w
            if node in first_wave and first_wave[node] < wv \
                    and node in cand[cls[i]]:
                repicked += 1
            first_wave.setdefault(node, wv)
        assert repicked > 0

    @pytest.mark.parametrize("classes", [2, None])
    def test_exception_pins_in_and_out_of_shortlist(self, classes):
        """`exc` pins to a column inside the class shortlist (the slot
        survives the elementwise pin test) and to one outside it (every
        slot masks out: the bound check fails and the replay — or, for
        the multistart, the whole-chunk rerun — resolves it exactly)."""
        rng = np.random.default_rng(1234)
        n, p, k = 72, 20, 8
        args, masks = _problem(rng, n=n, p=p, r=2, classes=classes)
        plain, _ = _shortlist_tables(args, masks, k=k,
                                     strategy="LeastAllocated")
        cand = np.asarray(plain["sl_cand"])
        cls = np.asarray(plain["sl_class"])
        exc = np.full((p,), -1, np.int32)
        inside = [1, 6, 11]
        outside = [3, 8, 15]
        for i in inside:
            exc[i] = cand[cls[i], i % k]
        for i in outside:
            exc[i] = next(c for c in range(n) if c not in cand[cls[i]])
        args["exc"] = jnp.asarray(exc)
        sl, sl1 = _shortlist_tables(args, masks, k=k,
                                    strategy="LeastAllocated", exc=exc)
        ref = np.asarray(solver.greedy_assign_rescoring(
            strategy="LeastAllocated", **args))
        # not vacuous: pins of both kinds land
        assert any(ref[i] == exc[i] for i in inside)
        assert any(ref[i] == exc[i] for i in outside)
        for w in (2, 8):
            a, nfall, _, _ = solver.greedy_assign_rescoring_shortlist_wave(
                strategy="LeastAllocated", wave_w=w, **sl, **args)
            np.testing.assert_array_equal(np.asarray(a), ref)
            assert int(nfall) > 0      # the outside pins replayed
        multi = _multi_orders(rng, p, 3)
        mref, _ = solver.multistart_greedy_assign_shortlist(
            strategy="LeastAllocated", **multi, **sl1, **args)
        a, _, _, _ = solver.multistart_greedy_assign_shortlist_wave(
            strategy="LeastAllocated", wave_w=4, **multi, **sl, **args)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(mref))

    @pytest.mark.parametrize("wave_w", [1, 2, 8, 64])
    @pytest.mark.parametrize("entry", ["single", "multistart"])
    def test_widths_and_orders(self, entry, wave_w):
        """W ∈ {1, 2, 8, 64 > P} on class tables, single order and the
        multistart entry under four orders."""
        rng = np.random.default_rng(4321)
        p = 28
        args, masks = _problem(rng, n=120, p=p, r=2, classes=3)
        sl, sl1 = _shortlist_tables(args, masks, k=p,
                                    strategy="LeastAllocated")
        if entry == "single":
            ref = np.asarray(solver.greedy_assign_rescoring(
                strategy="LeastAllocated", **args))
            a, _, com, rep = solver.greedy_assign_rescoring_shortlist_wave(
                strategy="LeastAllocated", wave_w=wave_w, **sl, **args)
        else:
            multi = _multi_orders(rng, p, 4)
            ref, _ = solver.multistart_greedy_assign_shortlist(
                strategy="LeastAllocated", **multi, **sl1, **args)
            ref = np.asarray(ref)
            a, _, com, rep = solver.multistart_greedy_assign_shortlist_wave(
                strategy="LeastAllocated", wave_w=wave_w, **multi, **sl,
                **args)
        np.testing.assert_array_equal(np.asarray(a), ref)
        assert int(com) + int(rep) == p


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            if hasattr(x, "jaxpr") and hasattr(x.jaxpr, "eqns"):
                yield x.jaxpr           # ClosedJaxpr
            elif hasattr(x, "eqns"):
                yield x


def _loops_stacking(jaxpr, shape):
    """The `while` loops under `jaxpr` that carry a stacked output whose
    trailing dimensions are `shape` — how `solver._scan_real` appears in
    a trace: (steps, W) for a wave scan, whatever a vmap put in front."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "while" and any(
                v.aval.shape[-len(shape):] == shape
                for v in eqn.params["body_jaxpr"].jaxpr.outvars):
            yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _loops_stacking(sub, shape)


def _gathered_index_rows(jaxpr):
    """Index rows of every gather under `jaxpr`: the count of separate
    lookups, whatever the width of the slice each one returns."""
    rows = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            rows += int(np.prod(eqn.invars[1].aval.shape[:-1]))
        for sub in _sub_jaxprs(eqn):
            rows += _gathered_index_rows(sub)
    return rows


class TestShortlistWaveGatherBudget:
    """Structural pin (abstract trace, CPU, a count and not a speed): the
    lookups inside one wave step of the shortlist wave scan, at the
    50,000-node cell's shapes. A TPU gathers element by element at
    scalar pace, and the per-member form of this scan — every member
    gathering node state, planes and sc0 through its own (K+P)-wide
    candidate row, 1,048,576 index rows a step and order in eight
    distinct gathers (1,319,232 in ten before XLA merges two) — set the
    pace of the 50k drain (PERF.md, PR 26). A refactor must not put it
    back."""

    PER_MEMBER_FORM = 8 * 64 * 2048

    @pytest.mark.parametrize("classes,budget", [
        (2, PER_MEMBER_FORM // 64),      # class planes: today 6,912
        (1024, PER_MEMBER_FORM // 8),    # per-pod planes: today 74,240
    ])
    def test_gathered_rows_per_wave_step(self, classes, budget):
        n, p, k, w, r, orders = 50_176, 1024, 1024, 64, 2, 4
        f32, i32 = jnp.float32, jnp.int32
        S = jax.ShapeDtypeStruct
        args = dict(
            req_q=S((p, r), i32), req_nz_q=S((p, r), i32),
            free_q=S((n, r), i32), free_pods=S((n,), i32),
            used_nz_q=S((n, r), i32), alloc_q=S((n, r), i32),
            mask=S((classes, n), jnp.bool_),
            static_scores=S((classes, n), f32),
            fit_col_w=S((r,), f32), bal_col_mask=S((r,), jnp.bool_),
            shape_u=S((2,), f32), shape_s=S((2,), f32),
            w_fit=S((), f32), w_bal=S((), f32),
            perms=S((orders, p), i32), gang_onehot=S((p, 16), f32),
            gang_required=S((16,), f32),
            sl_class=S((p,), i32), sl_cand=S((classes, k), i32),
            sl_val=S((classes, k), f32), sl_thresh=S((classes,), f32),
            has_node=S((p,), jnp.bool_), rows=S((p,), i32),
            exc=S((p,), i32))
        closed = jax.make_jaxpr(
            lambda kw: solver.multistart_greedy_assign_shortlist_wave(
                strategy="LeastAllocated", wave_w=w, **kw))(args)
        # the wave scan is the loop that stacks (P/W, W) picks (the
        # whole-chunk rerun behind the poison cond stacks (P,))
        wave_scans = list(_loops_stacking(closed.jaxpr, (p // w, w)))
        assert len(wave_scans) == 1
        rows = _gathered_index_rows(
            wave_scans[0].params["body_jaxpr"].jaxpr)
        per_order = rows / orders
        assert 0 < per_order <= budget, \
            f"{per_order:.0f} gathered index rows a wave step and " \
            f"order at S={classes}; budget {budget}"


class TestSpreadWaveParity:
    def _spread_problem(self, rng, n, p, domains, cons):
        """Contested spread: few domains, tight maxSkew, every pod
        gating AND contributing — commits open/close domains mid-wave,
        the structural replay rule's worst case."""
        r = 2
        args, _ = _problem(rng, n=n, p=p, r=r)
        dom = np.zeros((n, domains), np.float32)
        for i in range(n):
            dom[i, i % domains] = 1.0
        cid = np.zeros((domains, cons), np.float32)
        for d in range(domains):
            cid[d, d % cons] = 1.0
        applies = (rng.random((p, cons)) > 0.3).astype(np.float32)
        contrib = np.maximum(
            applies, (rng.random((p, cons)) > 0.5)).astype(np.float32)
        sp = dict(
            dom_onehot=jnp.asarray(dom), cid_onehot=jnp.asarray(cid),
            dom_counts=jnp.asarray(
                rng.integers(0, 3, size=(domains,)).astype(np.float32)),
            max_skew=jnp.asarray(
                rng.integers(1, 3, size=(cons,)).astype(np.float32)),
            min_ok=jnp.ones((cons,), jnp.float32),
            has_key_nc=jnp.asarray(np.ones((n, cons), np.float32)),
            applies=jnp.asarray(applies), contributes=jnp.asarray(contrib))
        return args, sp

    def test_contested_domains_bit_identity(self):
        for seed in range(4):
            rng = np.random.default_rng(500 + seed)
            args, sp = self._spread_problem(rng, n=30, p=21, domains=5,
                                            cons=2)
            ref, ref_dc = solver.greedy_assign_rescoring_spread(
                strategy="LeastAllocated", **sp, **args)
            for w in WIDTHS + (21,):
                a, dc, com, rep = solver.greedy_assign_rescoring_spread_wave(
                    strategy="LeastAllocated", wave_w=w, **sp, **args)
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(ref),
                                              err_msg=f"W={w}")
                np.testing.assert_array_equal(np.asarray(dc),
                                              np.asarray(ref_dc))
                assert int(com) + int(rep) == 21

    def test_contribute_only_pods_keep_speculating(self):
        """Pods that CONTRIBUTE to counts but carry no gating constraint
        (app = 0) must not force replays — only gated members after a
        count-moving commit replay. Template pods on uniform nodes are
        the regime where the spread-free wave provably commits 100%
        (TestRescoringWaveParity.test_uniform_template...), so any
        replay here would be the structural rule misfiring on app=0."""
        n, p, r, domains, cons = 40, 16, 2, 4, 2
        args = dict(
            req_q=jnp.asarray(np.full((p, r), 500, np.int32)),
            req_nz_q=jnp.asarray(np.full((p, r), 500, np.int32)),
            free_q=jnp.asarray(np.full((n, r), 8000, np.int32)),
            free_pods=jnp.asarray(np.full((n,), 110, np.int32)),
            used_nz_q=jnp.asarray(np.zeros((n, r), np.int32)),
            alloc_q=jnp.asarray(np.full((n, r), 8000, np.int32)),
            mask=jnp.asarray(np.ones((p, n), np.bool_)),
            static_scores=jnp.asarray(np.zeros((p, n), np.float32)),
            fit_col_w=jnp.ones((r,), jnp.float32),
            bal_col_mask=jnp.ones((r,), np.bool_),
            shape_u=jnp.zeros((2,), jnp.float32),
            shape_s=jnp.zeros((2,), jnp.float32),
            w_fit=jnp.float32(1.0), w_bal=jnp.float32(1.0))
        dom = np.zeros((n, domains), np.float32)
        for i in range(n):
            dom[i, i % domains] = 1.0
        cid = np.zeros((domains, cons), np.float32)
        for d in range(domains):
            cid[d, d % cons] = 1.0
        sp = dict(
            dom_onehot=jnp.asarray(dom), cid_onehot=jnp.asarray(cid),
            dom_counts=jnp.asarray(np.zeros((domains,), np.float32)),
            max_skew=jnp.asarray(np.ones((cons,), np.float32)),
            min_ok=jnp.ones((cons,), jnp.float32),
            has_key_nc=jnp.asarray(np.ones((n, cons), np.float32)),
            applies=jnp.zeros((p, cons), jnp.float32),
            contributes=jnp.ones((p, cons), jnp.float32))
        ref, ref_dc = solver.greedy_assign_rescoring_spread(
            strategy="LeastAllocated", **sp, **args)
        a, dc, com, rep = solver.greedy_assign_rescoring_spread_wave(
            strategy="LeastAllocated", wave_w=8, **sp, **args)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(ref))
        np.testing.assert_array_equal(np.asarray(dc), np.asarray(ref_dc))
        assert int(rep) == 0 and int(com) == p


class TestBackendE2EParity:
    def test_backend_wave_vs_kill_switch(self):
        """End-to-end through TPUBackend: flagless wavefront assignments
        equal KTPU_WAVEFRONT=0 at W ∈ {1, 4, 8} and the W=chunk extreme
        (KTPU_WAVE_WIDTH=chunk)."""
        from test_tpu_backend import default_fwk
        from kubernetes_tpu.api.types import make_node, make_pod
        from kubernetes_tpu.ops.backend import TPUBackend
        from kubernetes_tpu.scheduler.cache import SchedulerCache
        from kubernetes_tpu.scheduler.types import PodInfo
        from kubernetes_tpu.utils import flags

        rng = np.random.default_rng(11)
        cache = SchedulerCache()
        for i in range(60):
            cache.add_node(make_node(
                f"n{i}", allocatable={"cpu": str(2 + int(rng.integers(6))),
                                      "memory": "16Gi", "pods": "16"}))
        snap = cache.update_snapshot()
        pods = [PodInfo(make_pod(
            f"p{i}", requests={"cpu": f"{250 * (1 + int(rng.integers(4)))}m",
                               "memory": "512Mi"},
            uid=f"u{i}")) for i in range(70)]
        fwk = default_fwk()
        with flags.scoped_set("KTPU_WAVEFRONT", "0"):
            base, _ = TPUBackend(max_batch=32, mesh=None).assign(
                pods, snap, fwk)
        for w in (1, 4, 8, 32):
            with flags.scoped_set("KTPU_WAVE_WIDTH", str(w)):
                got, _ = TPUBackend(max_batch=32, mesh=None).assign(
                    pods, snap, fwk)
            assert got == base, f"W={w} diverged from kill switch"

    def test_backend_wave_sharded_mesh(self):
        """Wavefront under the backend's auto-partitioned mesh at shard
        counts {1, 4, 8}: assignments equal the single-device backend."""
        from test_tpu_backend import default_fwk
        from kubernetes_tpu.api.types import make_node, make_pod
        from kubernetes_tpu.ops.backend import TPUBackend
        from kubernetes_tpu.parallel import build_mesh
        from kubernetes_tpu.scheduler.cache import SchedulerCache
        from kubernetes_tpu.scheduler.types import PodInfo

        cache = SchedulerCache()
        for i in range(64):
            cache.add_node(make_node(
                f"m{i}", allocatable={"cpu": "8", "memory": "32Gi",
                                      "pods": "110"}))
        snap = cache.update_snapshot()
        pods = [PodInfo(make_pod(
            f"q{i}", requests={"cpu": "500m", "memory": "1Gi"},
            uid=f"w{i}")) for i in range(48)]
        fwk = default_fwk()
        base, _ = TPUBackend(max_batch=16, mesh=None).assign(
            pods, snap, fwk)
        for shards in _mesh_sizes():
            got, _ = TPUBackend(max_batch=16,
                                mesh=build_mesh(shards)).assign(
                pods, snap, fwk)
            assert got == base, f"shards={shards} diverged"
