"""Preferred pod (anti-)affinity through `TPUBackend.assign`, against the
host scheduler one pod at a time, plugin by plugin.

A pod group whose InterPodAffinity score the assign's own placements
move is CARRIED: the scan recomputes its raw score at every step from
counts it carries (ops/solver.py `_ipa_score`) and normalises it over
that step's feasible nodes, and the counts chain from chunk to chunk on
the device. Here the same pods go through one `assign()` and through
the default profile's plugins one at a time (the highest total, the
lowest node index on ties), on seeded random clusters of 64 nodes in
two zones, for chunks of 1, 7 and 300, chunks in flight, a hostname and
a zone key, preferred anti-affinity, carried and plain groups in one
chunk, residents whose required affinity weighs back
(hardPodAffinityWeight), a spread pod in the chunk, and the routes a
carried chunk may be asked to take (a plan, a wave, a shortlist,
per-pod planes). And: a group that is not carried keeps the static row
it had, byte for byte.
"""

import copy
import random

import numpy as np
import pytest

from kubernetes_tpu.api.types import make_node, make_pod
from kubernetes_tpu.metrics.registry import SchedulerMetrics
from kubernetes_tpu.ops import TPUBackend
from kubernetes_tpu.scheduler.cache import SchedulerCache
from kubernetes_tpu.scheduler.framework import CycleState, Framework
from kubernetes_tpu.scheduler.plugins.registry import (
    DEFAULT_SCORE_WEIGHTS,
    build_plugins,
)
from kubernetes_tpu.scheduler.types import PodInfo

HOST = "kubernetes.io/hostname"
ZONE = "topology.kubernetes.io/zone"
#: a score within this of the host's best is a float32 rounding tie
TIE = 1e-3


def _term(app, key, weight):
    return {"weight": weight, "podAffinityTerm": {
        "labelSelector": {"matchLabels": {"app": app}}, "topologyKey": key}}


def _affinity(pref=(), anti=(), required=()):
    out = {}
    if pref:
        out["podAffinity"] = {
            "preferredDuringSchedulingIgnoredDuringExecution": list(pref)}
    if required:
        out.setdefault("podAffinity", {})[
            "requiredDuringSchedulingIgnoredDuringExecution"] = list(required)
    if anti:
        out["podAntiAffinity"] = {
            "preferredDuringSchedulingIgnoredDuringExecution": list(anti)}
    return out or None


#: pending pod groups: (labels, affinity)
GROUPS = {
    # co-locate by host, weight 3
    "web": ({"app": "web"}, _affinity(pref=[_term("web", HOST, 3)])),
    # near web by zone
    "db": ({"app": "db"}, _affinity(pref=[_term("web", ZONE, 2)])),
    # keep apart by host
    "cache": ({"app": "cache"}, _affinity(anti=[_term("cache", HOST, 4)])),
    # carries nothing, selected by web's term: moved by web's placements
    "webish": ({"app": "web"}, None),
    # carries nothing, selected by nothing: plain
    "plain": ({}, None),
}


def _cluster(seed, n_nodes=64):
    """Nodes of three sizes in two zones; residents of every group and
    some that weigh back: a preferred term, or a required affinity term
    selecting `db` (× hardPodAffinityWeight)."""
    rng = random.Random(seed)
    cache = SchedulerCache()
    for i in range(n_nodes):
        cache.add_node(make_node(
            f"n{i}", labels={ZONE: f"z{i % 2}"},
            allocatable={"cpu": str(rng.choice([2, 4, 8])),
                         "memory": "32Gi", "pods": "110"}))
    residents = []
    for j in range(n_nodes // 2):
        kind = rng.choice(["web", "db", "cache", "plain", "hard"])
        if kind == "hard":
            labels, aff = {"app": "api"}, _affinity(required=[{
                "labelSelector": {"matchLabels": {"app": "db"}},
                "topologyKey": rng.choice([HOST, ZONE])}])
        else:
            labels, aff = GROUPS[kind]
        pod = make_pod(f"r{j}", uid=f"r{j}", labels=labels, affinity=aff,
                       node_name=f"n{rng.randrange(n_nodes)}",
                       requests={"cpu": "200m", "memory": "256Mi"})
        residents.append(pod)
        cache.add_pod(PodInfo(pod))
    return cache


def _pending(seed, n, groups):
    rng = random.Random(seed + 1)
    out = []
    for j in range(n):
        labels, aff = GROUPS[rng.choice(groups)]
        out.append(make_pod(
            f"p{j}", uid=f"p{j}", labels=copy.deepcopy(labels),
            affinity=copy.deepcopy(aff),
            requests={"cpu": f"{rng.choice([100, 250, 500])}m",
                      "memory": "256Mi"}))
    return out


def _fwk():
    return Framework(build_plugins(), DEFAULT_SCORE_WEIGHTS)


def _host_replay(cache, pods, device):
    """The host scheduler one pod at a time on a copy of the cache,
    placing each pod where the DEVICE put it (so that a float32 rounding
    tie cannot send the two apart); returns per pod (host's choice, the
    device's choice, host score of each, host best)."""
    fwk = _fwk()
    rows = []
    for pod in pods:
        pi = PodInfo(copy.deepcopy(pod))
        snap = cache.update_snapshot()
        state = CycleState()
        fwk.run_pre_filter(state, pi, snap)
        feasible = [ni for ni in snap.nodes
                    if fwk.run_filters(state, pi, ni).is_success()]
        got = device[pi.key]
        if not feasible:
            rows.append((None, got, None, None, None))
            continue
        fwk.run_pre_score(state, pi, feasible)
        scores = fwk.run_scores(state, pi, feasible)
        best = max(scores.values())
        want = next(ni.name for ni in snap.nodes
                    if scores.get(ni.name) == best)
        rows.append((want, got, scores.get(want), scores.get(got), best))
        if got is not None:
            placed = copy.deepcopy(pod)
            placed["spec"]["nodeName"] = got
            cache.add_pod(PodInfo(placed))
    return rows


def _check(rows):
    """Every pod lands where the host scheduler puts it; a different
    node is allowed only at a float32 rounding tie of the host's best."""
    exact = 0
    for j, (want, got, s_want, s_got, best) in enumerate(rows):
        if want is None:
            assert got is None, (j, got)
            continue
        assert got is not None, (j, want)
        if got == want:
            exact += 1
            continue
        assert s_got is not None and s_got >= best - TIE, (
            f"pod {j}: device {got} scores {s_got}, host {want} {best}")
    return exact


def _assign(cache, pods, max_batch, metrics=None):
    backend = TPUBackend(max_batch=max_batch, mesh=None)
    backend.metrics = metrics
    got, _ = backend.assign([PodInfo(copy.deepcopy(p)) for p in pods],
                            cache.update_snapshot(), _fwk())
    return got, backend


ALL = ["web", "db", "cache", "webish", "plain"]


@pytest.mark.parametrize("n_pods,max_batch", [
    pytest.param(1, 8, id="batch-of-one"),
    pytest.param(7, 8, id="chunk-of-7"),
    pytest.param(300, 512, id="chunk-of-300"),
    pytest.param(60, 16, id="chunks-in-flight"),
])
@pytest.mark.parametrize("seed", [5, 17])
def test_assign_equals_the_host_scheduler_one_pod_at_a_time(
        n_pods, max_batch, seed):
    cache = _cluster(seed)
    # a lone pod is carried when it carries a term: its own group moves
    pods = _pending(seed, n_pods, ALL if n_pods > 1 else ["web"])
    metrics = SchedulerMetrics()
    got, _ = _assign(_cluster(seed), pods, max_batch, metrics)
    rows = _host_replay(cache, pods, got)
    exact = _check(rows)
    assert exact >= len(rows) - max(1, len(rows) // 50)
    assert metrics.affinity_score_classes.value(kind="carried") >= 1
    # a carried chunk never takes the plan
    assert metrics.solver_optimal_solves.value() == 0


@pytest.mark.parametrize("key", [HOST, ZONE])
def test_one_group_packs_as_the_host_does(key):
    """"Co-locate my replicas" alone, by host or by zone, on equal nodes:
    the host fills the preferred domain pod after pod; so does the
    device, across four chunks in flight."""
    cache = SchedulerCache()
    for i in range(64):
        cache.add_node(make_node(
            f"n{i}", labels={ZONE: f"z{i % 2}"},
            allocatable={"cpu": "2", "memory": "8Gi", "pods": "110"}))
    snap0 = copy.deepcopy(cache)
    pods = [make_pod(f"p{j}", uid=f"p{j}", labels={"app": "web"},
                     affinity=_affinity(pref=[_term("web", key, 1)]),
                     requests={"cpu": "300m", "memory": "256Mi"})
            for j in range(64)]
    got, _ = _assign(snap0, pods, 16)
    rows = _host_replay(cache, pods, got)
    assert _check(rows) == len(pods)
    used = {got[f"default/p{j}"] for j in range(64)}
    if key == HOST:
        assert len(used) == 11        # six 300m pods fill a 2-CPU node
    else:
        assert {int(n[1:]) % 2 for n in used} == {0}


@pytest.mark.parametrize("env", [
    pytest.param({"KTPU_SOLVE_MODE": "optimal"}, id="plan-asked"),
    pytest.param({"KTPU_WAVE_WIDTH": "32"}, id="wave-asked"),
    pytest.param({"KTPU_SHORTLIST_K": "8"}, id="shortlist-asked"),
    pytest.param({"KTPU_CLASS_PAD": "0"}, id="per-pod-planes"),
])
def test_every_route_a_carried_chunk_is_asked_for_keeps_the_scan(
        monkeypatch, env):
    for name, value in env.items():
        monkeypatch.setenv(name, value)
    seed = 23
    cache = _cluster(seed)
    pods = _pending(seed, 90, ALL)
    metrics = SchedulerMetrics()
    got, _ = _assign(_cluster(seed), pods, 32, metrics)
    rows = _host_replay(cache, pods, got)
    assert _check(rows) >= len(rows) - 2
    assert metrics.solver_optimal_solves.value() == 0


def test_a_spread_pod_in_the_chunk_keeps_the_carry():
    """A DoNotSchedule zone spread pod in the chunk sends it down the
    spread scan, which carries the InterPodAffinity counts too."""
    seed = 31
    cache = _cluster(seed)
    pods = _pending(seed, 40, ["web", "db", "webish"])
    for j in range(0, 40, 5):
        pods[j]["spec"]["topologySpreadConstraints"] = [{
            "maxSkew": 1, "topologyKey": ZONE,
            "whenUnsatisfiable": "DoNotSchedule",
            "labelSelector": {"matchLabels": {"app": "db"}}}]
    got, _ = _assign(_cluster(seed), pods, 16)
    rows = _host_replay(cache, pods, got)
    assert _check(rows) >= len(rows) - 1


def test_plain_groups_are_not_carried_and_keep_the_static_path():
    """Pods that carry no term, where no resident's term selects them:
    nothing is carried, no span opens, the chunk keeps its usual route."""
    seed = 7
    metrics = SchedulerMetrics()
    pods = _pending(seed, 20, ["plain"])
    _assign(_cluster(seed), pods, 32, metrics)
    assert metrics.affinity_score_classes.value(kind="carried") == 0
    assert metrics.affinity_score_duration.count() == 0


# -- the static rows of groups that are not carried ----------------------------

def _rows_before_the_split(compiler, pod, hard_weight, feasible):
    """`AffinityCompiler.score_row` as it read before it was split into
    `score_parts` and the fold over the feasible nodes: term by term."""
    from kubernetes_tpu.api.labels import from_label_selector, ns_contains
    from kubernetes_tpu.scheduler.plugins.interpodaffinity import (
        resolve_term_namespaces as _term_ns,
    )
    row = np.zeros((compiler.n_pad,), dtype=np.float32)
    for sign, terms in ((1.0, pod.preferred_affinity_terms),
                        (-1.0, pod.preferred_anti_affinity_terms)):
        for term in terms:
            t = term.get("podAffinityTerm") or {}
            counts = compiler.counts_for(
                t.get("labelSelector"), _term_ns(t, pod.namespace, None))
            per_node, has_key = compiler._masked_presence(
                counts, t.get("topologyKey", ""), feasible)
            if sign > 0:
                row += float(term.get("weight", 1)) * np.where(
                    has_key, per_node, 0.0)
            else:
                row -= float(term.get("weight", 1)) * np.where(
                    has_key, per_node, 0.0)
    for key, (carriers, term, owner_ns, is_hard) in \
            compiler.resident_score.items():
        hit = ns_contains(_term_ns(term, owner_ns, None), pod.namespace) \
            and from_label_selector(term.get("labelSelector")).matches(
                pod.labels)
        if not hit:
            continue
        per_node, has_key = compiler._masked_presence(
            carriers, term.get("topologyKey", ""), feasible)
        w = hard_weight if is_hard else 1.0
        row += w * np.where(has_key, per_node, 0.0)
    row[compiler.n_real:] = 0.0
    return row


@pytest.mark.parametrize("seed", [3, 9, 27])
def test_static_rows_are_byte_identical_to_the_term_by_term_sum(seed):
    from kubernetes_tpu.ops.affinity import AffinityCompiler
    cache = _cluster(seed)
    snap = cache.update_snapshot()
    compiler = AffinityCompiler(snap, 64)
    rng = np.random.default_rng(seed)
    for pod in _pending(seed, 30, ALL):
        pi = PodInfo(pod)
        feasible = rng.random(64) < 0.7
        for hw in (1.0, 5.0):
            want = _rows_before_the_split(compiler, pi, hw, feasible)
            got = compiler.score_row(pi, hw, feasible)
            assert got.tobytes() == want.tobytes()
