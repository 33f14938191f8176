"""The chunk scans stop at the chunk's last real pod (PR 31).

A chunk is padded to P pods so that one program serves every chunk; the
fused program is handed the chunk's real pod count as a traced scalar and
every scan it can pick runs ceil(p_real / W) steps instead of P / W
(`solver._scan_real`). Three questions:

- is what the program returns — assignments, the five tail counters, the
  used pack, the spread domain counts — bit for bit what the fixed-length
  scan returns, on every route and for chunks from one pod to a full one?
  The fixed-length scan is kept HERE as the reference (`lax.scan` over the
  padded chunk and a replay of all W members, as the parent had them): it
  is patched over the two helpers that carry the trip count, and the jit
  caches are dropped on each side of it, so it is never a runtime path;
- is the real count no compile key;
- does the loop stay a loop under the multistart vmap (an unbatched
  predicate), where a batched one would turn it into a select that runs
  every step.
"""

import random
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from kubernetes_tpu.api.types import make_pod
from kubernetes_tpu.ops import backend as backend_mod
from kubernetes_tpu.ops import solver
from kubernetes_tpu.ops.backend import TPUBackend
from kubernetes_tpu.scheduler.types import PodInfo
from mesh_parity import (
    GREEDY,
    Case,
    gang_fwk,
    gang_pods,
    ran,
    run_case,
    spread_pods,
    template_pods,
    uniform_cluster,
)
from test_tpu_backend import TOL_POOL, default_fwk, random_cluster
from test_wavefront_solver import _sub_jaxprs

P = 1024
P_REALS = (1, 2, 7, 40, 63, 64, 500, 512, 1023, 1024)
W = 8    # 63 and 64 pods are eight waves both, 7 are one, 500 are 63


# -- the reference: the parent's loops ---------------------------------------

def _fixed_length_scan(step, carry0, xs, n_steps=None, y_shape=()):
    return lax.scan(step, carry0, xs)


def _replay_every_member(p_real, p, wave_w, n_waves):
    """(all steps, a replay over all W members, nothing to add back)."""
    return None, jnp.full((n_waves,), wave_w, jnp.int32), 0


class _Reference:
    """`with _Reference():` — the fixed-length loops in place of
    `_scan_real` / `_wave_live`, traced afresh on the way in and out."""

    def __enter__(self):
        self._kept = (solver._scan_real, solver._wave_live)
        solver._scan_real = _fixed_length_scan
        solver._wave_live = _replay_every_member
        jax.clear_caches()

    def __exit__(self, *exc):
        solver._scan_real, solver._wave_live = self._kept
        jax.clear_caches()


# -- the routes, at P = 1,024 -------------------------------------------------

def _plain(n: int, seed: int):
    """`n` pods of two templates, a third of them tolerating the fleet's
    taints (two more classes)."""
    rng = random.Random(seed)
    shapes = ({"cpu": "250m", "memory": "256Mi"},
              {"cpu": "500m", "memory": "1Gi"})
    return [PodInfo(make_pod(
        f"pend-{i}", uid=f"uid-{i}", requests=rng.choice(shapes),
        tolerations=TOL_POOL if rng.random() < 0.3 else None))
        for i in range(n)]


def _fleet(seed: int, n: int = 160):
    return random_cluster(random.Random(seed), n)


def serial(n):
    return Case(_fleet(3), _plain(n, 3), chunk=P,
                env={**GREEDY, "KTPU_WAVEFRONT": "0",
                     "KTPU_SHORTLIST_K": "0"},
                expect=ran(use_spread=False, shortlist_k=0, wave_w=0,
                           solve_mode="greedy", class_mode=True))


def wave(n):
    return Case(_fleet(5), _plain(n, 5), chunk=P,
                env={**GREEDY, "KTPU_WAVE_WIDTH": str(W),
                     "KTPU_SHORTLIST_K": "0"},
                expect=ran(use_spread=False, shortlist_k=0, wave_w=W,
                           solve_mode="greedy"))


def shortlist(n):
    return Case(_fleet(9), _plain(n, 9), chunk=P,
                env={**GREEDY, "KTPU_WAVEFRONT": "0",
                     "KTPU_SHORTLIST_K": "16"},
                expect=ran(use_spread=False, shortlist_k=16, wave_w=0,
                           block_w=0, class_mode=True))


def shortlist_wave(n):
    return Case(_fleet(11), _plain(n, 11), chunk=P,
                env={**GREEDY, "KTPU_WAVE_WIDTH": str(W),
                     "KTPU_SHORTLIST_K": "16"},
                expect=ran(use_spread=False, shortlist_k=16, wave_w=W,
                           block_w=0))


def shortlist_block(n):
    return Case(uniform_cluster(240), template_pods(n, 13), chunk=P,
                large_n=1,
                env={**GREEDY, "KTPU_WAVE_WIDTH": str(W),
                     "KTPU_SHORTLIST_K": "16", "KTPU_BLOCK_WIDTH": "16"},
                expect=ran(use_spread=False, shortlist_k=16, wave_w=W,
                           block_w=16))


def optimal(n):
    """The Sinkhorn plan, then the serial scan rounds it."""
    return Case(_fleet(17), _plain(n, 17), chunk=P,
                env={"KTPU_SOLVE_MODE": "optimal"},
                expect=ran(solve_mode="optimal", shortlist_k=0, wave_w=0,
                           class_mode=True))


def per_pod_planes(n):
    """A class cap of 1 against four classes: per-pod planes (C == P)
    from the second pod on; a lone pod is one class and keeps them."""
    return Case(_fleet(19), _plain(n, 19), chunk=P,
                env={**GREEDY, "KTPU_CLASS_PAD": "1",
                     "KTPU_WAVE_WIDTH": str(W)},
                expect=ran(shortlist_k=0, wave_w=W, solve_mode="greedy"))


def _pin_some(pods):
    """Every seventh pod pinned to a node (the exception column)."""
    for i in range(0, len(pods), 7):
        pods[i] = PodInfo(make_pod(
            f"pin-{i}", uid=f"pin-{i}", node_name=f"n{(37 * i) % 250}",
            requests={"cpu": "500m", "memory": "512Mi"}))
    return pods


def pinned(n):
    return Case(uniform_cluster(250), _pin_some(template_pods(n, 23)),
                chunk=P,
                env={**GREEDY, "KTPU_SHORTLIST_K": "0",
                     "KTPU_WAVE_WIDTH": str(W)},
                expect=ran(class_mode=True, shortlist_k=0, wave_w=W))


def pinned_shortlist(n):
    return Case(uniform_cluster(250), _pin_some(template_pods(n, 29)),
                chunk=P,
                env={**GREEDY, "KTPU_SHORTLIST_K": "16",
                     "KTPU_WAVEFRONT": "0"},
                expect=ran(class_mode=True, shortlist_k=16, wave_w=0))


def gang(n):
    """Two gangs among the chunk's pods where it has room for them: one
    that binds whole and one that cannot and is dropped whole."""
    pods = template_pods(n, 31)
    if n >= 12:
        pods[1:5] = gang_pods("fits", ["1"] * 4)
        pods[6:11] = gang_pods("bent", ["1", "1", "1", "16", "16"])
    return Case(uniform_cluster(200), pods, chunk=P,
                fwk=gang_fwk({"fits": 4, "bent": 5}),
                expect=ran(gang=n >= 12))


def spread_serial(n):
    return Case(uniform_cluster(96, zones=3), spread_pods(n, 41), chunk=P,
                env={**GREEDY, "KTPU_WAVEFRONT": "0",
                     "KTPU_SHORTLIST_K": "0"},
                expect=ran(use_spread=True, shortlist_k=0, wave_w=0))


def spread_wave(n):
    return Case(uniform_cluster(96, zones=3),
                spread_pods(n, 43, max_skew=2), chunk=P,
                env={**GREEDY, "KTPU_WAVE_WIDTH": str(W),
                     "KTPU_SHORTLIST_K": "0"},
                expect=ran(use_spread=True, shortlist_k=0, wave_w=W))


def spread_shortlist(n):
    return Case(uniform_cluster(120, zones=4), spread_pods(n, 47), chunk=P,
                env={**GREEDY, "KTPU_SHORTLIST_K": "16"},
                expect=ran(use_spread=True, shortlist_k=16, wave_w=0))


ROUTES = {f.__name__: f for f in (
    serial, wave, shortlist, shortlist_wave, shortlist_block,
    optimal, per_pod_planes, pinned, pinned_shortlist, gang,
    spread_serial, spread_wave, spread_shortlist)}


# -- one chunk through the backend, and all that the program returned --------

def _solve(case: Case):
    """(placements, [what each dispatched chunk's program returned],
    the statics each chunk ran with)."""
    chunks = []

    def seen(backend, out, ctx):
        got = {"assign_and_tail": np.asarray(out["assign_d"]),
               "used_pack": np.asarray(backend._dev_used)}
        if out["spread_used"]:
            got["dom_counts"] = np.asarray(ctx.spread["dev_counts"])
        chunks.append(got)

    got, _, statics = run_case(case, None, pytest.MonkeyPatch(), seen)
    return got, chunks, statics


@pytest.fixture(scope="module")
def reference():
    """{(route, p_real): what the fixed-length scan returned} — every
    case solved under the reference first, in one stretch, so that the
    jit caches are dropped twice and not once a case."""
    out = {}
    with _Reference():
        for name, build in ROUTES.items():
            for n in P_REALS:
                try:
                    out[name, n] = _solve(build(n))
                except Exception as exc:  # told by the case that needs it
                    out[name, n] = exc
    assert solver._scan_real is not _fixed_length_scan
    return out


@pytest.mark.parametrize("p_real", P_REALS)
@pytest.mark.parametrize("route", list(ROUTES))
def test_ragged_chunk_equals_the_fixed_length_scan(route, p_real, reference):
    want = reference[route, p_real]
    if isinstance(want, Exception):
        raise want
    want_got, want_chunks, want_statics = want
    case = ROUTES[route](p_real)
    got, chunks, statics = _solve(case)

    assert statics and case.expect(statics), statics
    assert statics == want_statics
    assert len(chunks) == len(want_chunks) == 1
    for name, value in want_chunks[0].items():
        np.testing.assert_array_equal(chunks[0][name], value, err_msg=name)
    assert chunks[0]["assign_and_tail"].shape == (P + 5,)
    assert (chunks[0]["assign_and_tail"][p_real:P] == -1).all()
    assert got == want_got
    assert any(got.values()), "nothing was placed: the case compares nothing"


# -- the real count is data, not a key ----------------------------------------

def test_real_count_is_no_compile_key():
    fn = backend_mod._mask_solve_update
    case = wave(2)
    b = TPUBackend(max_batch=P, mesh=None)
    with pytest.MonkeyPatch.context() as mp:
        for k, v in case.env.items():
            mp.setenv(k, v)
        b.assign(case.pods, case.snap, default_fwk())
        before = fn._cache_size()
        for n in (1, 3, 40, 600):
            b.assign(wave(n).pods, case.snap, default_fwk())
    assert before >= 1
    assert fn._cache_size() == before


# -- under the multistart vmap the loop stays a loop --------------------------

def _whiles(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "while":
            yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _whiles(sub)


def _while_conditions(stablehlo: str):
    """The text of each `stablehlo.while`'s condition region."""
    for m in re.finditer(r"stablehlo\.while", stablehlo):
        start = stablehlo.index("cond {", m.end())
        yield stablehlo[start:stablehlo.index("} do {", start)]


def _wave_problem(p=64, n=40, orders=4, r=2):
    rng = np.random.default_rng(7)
    i32, f32 = np.int32, np.float32
    return dict(
        req_q=rng.integers(1, 4, (p, r)).astype(i32),
        req_nz_q=rng.integers(1, 4, (p, r)).astype(i32),
        free_q=np.full((n, r), 64, i32), free_pods=np.full((n,), 110, i32),
        used_nz_q=np.zeros((n, r), i32), alloc_q=np.full((n, r), 64, i32),
        mask=np.ones((2, n), bool), static_scores=np.zeros((2, n), f32),
        fit_col_w=np.ones((r,), f32), bal_col_mask=np.ones((r,), bool),
        shape_u=np.array([0, 100], f32), shape_s=np.array([0, 10], f32),
        w_fit=f32(1), w_bal=f32(1),
        perms=np.tile(np.arange(p, dtype=i32), (orders, 1)),
        gang_onehot=np.zeros((p, 16), f32),
        gang_required=np.zeros((16,), f32),
        rows=np.zeros((p,), i32))


def test_multistart_loop_keeps_a_scalar_predicate():
    kw = _wave_problem()

    def solve(p_real):
        return solver.multistart_greedy_assign_wave(
            strategy="LeastAllocated", wave_w=8, p_real=p_real, **kw)

    loops = list(_whiles(jax.make_jaxpr(solve)(np.int32(3)).jaxpr))
    assert loops
    for eqn in loops:
        (pred,) = eqn.params["cond_jaxpr"].out_avals
        assert pred.shape == (), "a batched predicate: every step runs"
    conds = list(_while_conditions(
        jax.jit(solve).lower(np.int32(3)).as_text()))
    assert conds
    for text in conds:
        assert "stablehlo.reduce" not in text
        assert re.search(r"stablehlo\.compare\s+LT.*tensor<i32>", text)
    # and the check does see the trap: a count that differs by order
    # batches the predicate, which lowers to an any() over the orders.
    batched = jax.vmap(lambda n: solver.greedy_assign_rescoring_wave(
        strategy="LeastAllocated", wave_w=8, p_real=n,
        **{k: v for k, v in kw.items()
           if k not in ("perms", "gang_onehot", "gang_required")})[0])
    counts = np.array([3, 9], np.int32)
    trapped = list(_whiles(jax.make_jaxpr(batched)(counts).jaxpr))
    assert any(e.params["cond_jaxpr"].out_avals[0].shape == (2,)
               for e in trapped)
    assert any("stablehlo.reduce" in c for c in _while_conditions(
        jax.jit(batched).lower(counts).as_text()))


def test_a_skipped_step_is_not_run():
    """Steps past the real count never execute: a step body that fails
    the moment it runs on a padded row is not reached."""
    seen = []

    def step(carry, x):
        jax.debug.callback(lambda v: seen.append(int(v)), x)
        return carry + x, x

    carry, ys = jax.jit(lambda n: solver._scan_real(
        step, jnp.int32(0), jnp.arange(10, 20, dtype=jnp.int32), n))(
            np.int32(3))
    jax.effects_barrier()
    assert sorted(seen) == [10, 11, 12]
    assert int(carry) == 33
    np.testing.assert_array_equal(
        np.asarray(ys), [10, 11, 12] + [-1] * 7)
