"""Differential: the affinity compiler and the spread table ADVANCED by the
scheduler cache's changed-node log against ones built anew on the same
snapshot — bit for bit (`np.array_equal`), and against a plain recount of
the resident pods. The advanced table is the same table."""

import random

import numpy as np
import pytest

from kubernetes_tpu.api.labels import from_label_selector
from kubernetes_tpu.api.types import make_node, make_pod
from kubernetes_tpu.metrics.registry import SchedulerMetrics
from kubernetes_tpu.ops import TPUBackend
from kubernetes_tpu.ops.affinity import AffinityCompiler
from kubernetes_tpu.ops.backend import _AssignCtx
from kubernetes_tpu.scheduler.cache import SchedulerCache
from kubernetes_tpu.scheduler.framework import Framework
from kubernetes_tpu.scheduler.plugins.interpodaffinity import (
    NamespaceResolver,
)
from kubernetes_tpu.scheduler.plugins.registry import (
    DEFAULT_SCORE_WEIGHTS,
    build_plugins,
)
from kubernetes_tpu.scheduler.types import PodInfo, Snapshot

ZONE = "topology.kubernetes.io/zone"
HOSTNAME = "kubernetes.io/hostname"
ZONES = ["z1", "z2", "z3"]
#: the label signatures residents are stamped from; "app: extra" arrives later
SIGS = [{"app": "web"}, {"app": "db"}, {"app": "web", "tier": "front"},
        {"app": "cache"}]
N_PAD = 32


def _node(i: int, zone: str | None):
    labels = {HOSTNAME: f"n{i}"}
    if zone is not None:
        labels[ZONE] = zone
    return make_node(f"n{i}", labels=labels,
                     allocatable={"cpu": "64", "memory": "256Gi",
                                  "pods": "500"})


def _cluster(rng: random.Random, nodes: int = 24) -> SchedulerCache:
    """Nodes in three zones, two of them without the zone key."""
    cache = SchedulerCache()
    for i in range(nodes):
        cache.add_node(_node(i, None if i in (5, 17) else rng.choice(ZONES)))
    return cache


def _term(app: str, key: str) -> dict:
    return {"labelSelector": {"matchLabels": {"app": app}},
            "topologyKey": key}


def _resident(rng: random.Random, name: str, node: str,
              labels: dict | None = None, key: str | None = None,
              every: bool = False) -> PodInfo:
    """A bound pod; one in five carries terms of its own (the carriers),
    or `every` one a required anti-affinity term, as in the benchmark's
    anti-affinity cell. `key` = the topology key of those terms (None:
    drawn, hostname or zone)."""
    aff = None
    r = rng.random()
    if every:
        r *= 0.1
    if r < 0.1:
        aff = {"podAntiAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": [
                _term(rng.choice(["web", "db"]),
                      key or rng.choice([HOSTNAME, ZONE]))]}}
    elif r < 0.2:
        aff = {"podAffinity": {
            "preferredDuringSchedulingIgnoredDuringExecution": [
                {"weight": rng.randrange(1, 50), "podAffinityTerm":
                 _term(rng.choice(["web", "cache"]), key or ZONE)}]}}
    return PodInfo(make_pod(
        name, uid=name, node_name=node, affinity=aff,
        labels=dict(labels if labels is not None else rng.choice(SIGS)),
        namespace=rng.choice(["default", "other"]),
        requests={"cpu": "10m"}))


def _group_member(name: str, node: str, group: str, key: str,
                  namespace: str) -> PodInfo:
    """The cell's pod: labelled with its group, and carrying the one
    required anti-affinity term that selects the group."""
    return PodInfo(make_pod(
        name, uid=name, node_name=node, labels={"app": group},
        namespace=namespace, requests={"cpu": "10m"},
        affinity={"podAntiAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": [
                _term(group, key)]}}))


def _rich(name: str, node: str, key: str, namespace: str) -> PodInfo:
    """A pod with a term of every kind that weighs back in the score:
    preferred, preferred anti and required affinity (and a required
    anti-affinity term beside them)."""
    return PodInfo(make_pod(
        name, uid=name, node_name=node, labels={"app": "cache"},
        namespace=namespace, requests={"cpu": "10m"},
        affinity={
            "podAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": [
                    _term("db", key)],
                "preferredDuringSchedulingIgnoredDuringExecution": [
                    {"weight": 9, "podAffinityTerm": _term("web", key)}]},
            "podAntiAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": [
                    _term("cache", key)],
                "preferredDuringSchedulingIgnoredDuringExecution": [
                    {"weight": 4, "podAffinityTerm": _term("web", key)},
                    {"weight": 3, "podAffinityTerm": _term("db", ZONE)}]}}))


def _spread_constraint(app: str, skew: int = 2, **extra) -> dict:
    return dict({"maxSkew": skew, "topologyKey": ZONE,
                 "whenUnsatisfiable": "DoNotSchedule",
                 "labelSelector": {"matchLabels": {"app": app}}}, **extra)


def _pending() -> list[PodInfo]:
    """Pods whose rows read every kind of count the compiler keeps."""
    def pod(name, labels, ns="default", **kw):
        return PodInfo(make_pod(name, uid=name, labels=labels, namespace=ns,
                                requests={"cpu": "10m"}, **kw))
    return [
        pod("p-anti", {"app": "web"}, affinity={"podAntiAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": [
                _term("web", HOSTNAME), _term("db", ZONE)]}}),
        pod("p-aff", {"app": "db"}, ns="other", affinity={"podAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": [
                _term("cache", ZONE)]}}),
        pod("p-first", {"app": "extra"}, affinity={"podAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": [
                _term("extra", ZONE)]}}),
        pod("p-pref", {"app": "cache"}, affinity={"podAffinity": {
            "preferredDuringSchedulingIgnoredDuringExecution": [
                {"weight": 7, "podAffinityTerm": _term("web", ZONE)}]}}),
        pod("p-spread", {"app": "web"}, topology_spread_constraints=[
            _spread_constraint("web")]),
        pod("p-spread2", {"app": "db"}, ns="other",
            topology_spread_constraints=[
                _spread_constraint("db", 1, minDomains=3),
                _spread_constraint("db", 3, topologyKey=HOSTNAME)]),
    ]


class _Churn:
    """A seeded sequence of cache mutations of every kind the changed-node
    log reports: binds, deletes, a pod of a NEW signature, the last pod of a
    signature leaving, an assume with its forget, an update in place — and,
    for pods that carry terms: a group bound in one batch, the binding's
    update of its members, a pod with terms of every kind, and a group
    deleted down to the last carrier of its term."""

    def __init__(self, cache: SchedulerCache, rng: random.Random,
                 key: str | None = None, every: bool = False):
        self.cache, self.rng = cache, rng
        self.key, self.every = key, every
        self.live: list[str] = []       # keys of resident pods
        self.seq = 0
        self.assumed: str | None = None
        self.groups: dict[str, list[str]] = {}   # group -> its pods' keys

    def _name(self) -> str:
        self.seq += 1
        return f"r{self.seq}"

    def bind(self, count: int, labels: dict | None = None) -> None:
        names = sorted(self.cache.nodes)
        for _ in range(count):
            pi = _resident(self.rng, self._name(), self.rng.choice(names),
                           labels, self.key, self.every)
            self.cache.add_pod(pi)
            self.live.append(pi.key)

    def delete(self, count: int) -> None:
        for _ in range(min(count, len(self.live))):
            key = self.live.pop(self.rng.randrange(len(self.live)))
            self.cache.remove_pod(key)

    def bind_group(self, group: str, count: int,
                   namespace: str = "default") -> None:
        """A batch of the cell's pods: one group, a node each."""
        nodes = self.rng.sample(sorted(self.cache.nodes), count)
        for node in nodes:
            pi = _group_member(self._name(), node, group,
                               self.key or HOSTNAME, namespace)
            self.cache.add_pod(pi)
            self.groups.setdefault(group, []).append(pi.key)

    def confirm(self, keys: list[str]) -> None:
        """The binding's own event: a NEW PodInfo of the same pod on the
        same node replaces the one the cache holds."""
        for key in keys:
            old = self.cache._pod_states[key]["pod"]
            self.cache.update_pod(PodInfo(
                {**old.pod, "metadata": dict(old.pod["metadata"])}))

    def unbind(self, group: str, count: int) -> None:
        for _ in range(count):
            self.cache.remove_pod(self.groups[group].pop())

    def step(self, k: int) -> None:
        rng = self.rng
        if k == 2:
            self.bind_group("solo", 3)
            self.bind_group("solo", 2, "other")   # the same term, its own key
        elif k == 4:
            self.confirm(self.groups["solo"])
            pi = _rich(self._name(), "n4", self.key or ZONE, "other")
            self.cache.add_pod(pi)
            self.groups["rich"] = [pi.key]
        elif k == 5:
            self.unbind("solo", 4)      # one carrier of one key is left
        elif k == 7:
            self.unbind("solo", 1)      # ... and its key goes with it
            self.confirm(self.groups["rich"])
        elif k == 10:
            self.unbind("rich", 1)
        elif k == 3:
            self.bind(2, {"app": "extra"})      # a signature nobody had
        elif k == 6:
            # the last pods of that signature leave: a zero column stays
            for key in [key for key in self.live
                        if self.cache._pod_states[key]["pod"].labels
                        == {"app": "extra"}]:
                self.live.remove(key)
                self.cache.remove_pod(key)
        elif k == 8:
            pi = _resident(rng, self._name(), "n2", {"app": "web"})
            self.cache.assume_pod(pi, "n2")
            self.assumed = pi.key
        elif k == 9:
            self.cache.forget_pod(self.assumed)
        elif k == 11:
            # an update replaces the pod's object on its node (as the
            # binding's own event does to an assumed pod): here relabelled
            old = self.cache._pod_states[rng.choice(self.live)]["pod"]
            pod = {**old.pod, "metadata": {**old.pod["metadata"],
                                           "labels": {"app": "db"}}}
            self.cache.update_pod(PodInfo(pod))
        else:
            self.bind(rng.randrange(1, 9))
            if rng.random() < 0.6:
                self.delete(rng.randrange(1, 5))


def _plain_counts(snapshot, selector: dict, namespaces,
                  n_pad: int) -> np.ndarray:
    """Matching resident pods per node, counted one pod at a time."""
    sel = from_label_selector(selector)
    out = np.zeros((n_pad,), dtype=np.float32)
    for n, ni in enumerate(snapshot.nodes):
        out[n] = sum(1 for pi in ni.pods
                     if pi.namespace in namespaces and sel.matches(pi.labels))
    return out


def _same_answers(advanced: AffinityCompiler, fresh: AffinityCompiler,
                  snapshot) -> None:
    for app in ("web", "db", "cache", "extra", "nobody"):
        selector = {"matchLabels": {"app": app}}
        for namespaces in (("default",), ("other",), ("default", "other")):
            got = advanced.sigs.node_sig_count @ advanced.sigs.match_vec(
                selector, namespaces)
            want = fresh.sigs.node_sig_count @ fresh.sigs.match_vec(
                selector, namespaces)
            assert np.array_equal(got, want)
            assert np.array_equal(got, _plain_counts(
                snapshot, selector, namespaces, advanced.n_pad))
            assert np.array_equal(
                advanced.counts_for(selector, namespaces),
                fresh.counts_for(selector, namespaces))
    feasible = np.zeros((advanced.n_pad,), dtype=np.bool_)
    feasible[: len(snapshot.nodes): 2] = True
    for pod in _pending():
        assert np.array_equal(advanced.filter_row(pod), fresh.filter_row(pod))
        assert np.array_equal(advanced.symmetry_mask(pod),
                              fresh.symmetry_mask(pod))
        assert np.array_equal(advanced.score_row(pod, 1.0, feasible),
                              fresh.score_row(pod, 1.0, feasible))
        cs = pod.topology_spread_constraints
        if cs:
            assert np.array_equal(advanced.spread_filter_row(pod, cs),
                                  fresh.spread_filter_row(pod, cs))
            assert np.array_equal(advanced.spread_raw_scores(pod, cs),
                                  fresh.spread_raw_scores(pod, cs))
    # the carriers of residents' own terms: the keys of a compiler built
    # anew (none whose last carrier went), each with its vector and what
    # it holds of the term
    for name in ("resident_anti", "resident_score"):
        got, want = getattr(advanced, name), getattr(fresh, name)
        assert set(got) == set(want)
        assert len(got) == len(want) and bool(got) == bool(want)
        for key, (vec, *held) in want.items():
            assert np.array_equal(got[key][0], vec)
            assert list(got[key][1:]) == held     # term, owner_ns[, is_hard]
    # whether a resident weighs back in the score, read from what is kept
    # and walked from the pods
    weighs_back = any(
        pi.preferred_affinity_terms or pi.preferred_anti_affinity_terms
        or pi.required_affinity_terms
        for ni in snapshot.nodes for pi in ni.pods)
    plain = _pending()[-2]          # p-spread: no preferred term of its own
    assert TPUBackend._ipa_score_relevant(plain, advanced) == weighs_back
    assert TPUBackend._ipa_score_relevant(plain, fresh) == weighs_back
    assert TPUBackend._ipa_score_relevant(_pending()[3], advanced)  # p-pref


def _standing(seed: int, key: str | None = None, every: bool = False):
    """A cluster with thirty residents, the churn that will move it, a
    framework, and the batch of spread pods every table is built for."""
    rng = random.Random(seed)
    cache = _cluster(rng)
    churn = _Churn(cache, rng, key, every)
    churn.bind(30)
    fwk = Framework(build_plugins(), DEFAULT_SCORE_WEIGHTS)
    batch = [p for p in _pending() if p.topology_spread_constraints]
    return cache, churn, fwk, batch


def _backend() -> TPUBackend:
    backend = TPUBackend(max_batch=16, mesh=None)
    backend.metrics = SchedulerMetrics()
    backend._ns_resolver = None
    return backend


def _spread_plugin(fwk):
    return next(p for p in fwk.filter_plugins
                if p.NAME == "PodTopologySpread")


def _table(backend: TPUBackend, snapshot, fwk, batch) -> dict:
    """The spread table `backend` builds for `batch` at `snapshot`, as
    TPUBackend._start builds it."""
    ctx = _AssignCtx()
    ctx.chunks = [batch]
    ct = backend._tensors(snapshot)
    backend._build_spread_table(
        ctx, snapshot, ct, backend._affinity_compiler(snapshot, ct),
        _spread_plugin(fwk))
    return ctx.spread


def _same_table(got: dict, want: dict) -> None:
    assert got["tpl_cols"] == want["tpl_cols"]
    assert got["cons"] == want["cons"]
    for name in ("dom_onehot_host", "cid_onehot_host", "dev_counts",
                 "dev_dom", "dev_cid", "dev_haskey", "dev_min_ok",
                 "dev_skew"):
        assert np.array_equal(np.asarray(got[name]), np.asarray(want[name])), \
            name


def _plain_zone_counts(snapshot, app: str, namespace: str) -> list[float]:
    """Pods of `app` in `namespace` per zone, zones in the order the nodes
    first show them (the order of the table's domain columns), counted one
    pod at a time."""
    counts: dict[str, float] = {}
    for ni in snapshot.nodes:
        zone = ni.labels.get(ZONE)
        if zone is None:
            continue
        counts.setdefault(zone, 0.0)
        counts[zone] += sum(1 for pi in ni.pods if pi.namespace == namespace
                            and pi.labels.get("app") == app)
    return list(counts.values())


def _builds(backend: TPUBackend) -> tuple[int, int]:
    m = backend.metrics.affinity_compiler_builds
    return int(m.value(kind="full")), int(m.value(kind="delta"))


@pytest.mark.parametrize("seed", range(6))
def test_an_advanced_compiler_answers_as_one_built_anew(seed):
    cache, churn, fwk, batch = _standing(seed)
    kept = _backend()
    snapshot = cache.update_snapshot()
    advanced = AffinityCompiler(snapshot, N_PAD)
    _table(kept, snapshot, fwk, batch)
    for k in range(14):
        churn.step(k)
        snapshot = cache.update_snapshot()
        recounted = advanced.advance(snapshot, N_PAD)
        assert recounted is not None and 0 < recounted <= len(snapshot.nodes)
        _same_answers(advanced, AffinityCompiler(snapshot, N_PAD), snapshot)
        table = _table(kept, snapshot, fwk, batch)
        _same_table(table, _table(_backend(), snapshot, fwk, batch))
        # p-spread's one constraint is the table's first: its columns
        lo, hi = table["con_cols"][0]
        assert np.asarray(table["dev_counts"])[lo:hi].tolist() \
            == _plain_zone_counts(snapshot, "web", "default")
    assert advanced.sigs.node_sig_count.shape[1] > len(SIGS)  # the new one
    assert _builds(kept) == (1, 14)
    planes = kept.metrics.spread_table_builds
    assert (planes.value(planes="built"), planes.value(planes="kept")) \
        == (1, 14)


def _anti_key(group: str, key: str, namespace: str) -> str:
    return repr((_term(group, key), namespace))


@pytest.mark.parametrize("every", [False, True],
                         ids=["one-in-five", "every-resident"])
@pytest.mark.parametrize("key", [HOSTNAME, ZONE], ids=["hostname", "zone"])
@pytest.mark.parametrize("seed", [21, 22])
def test_carriers_come_and_go_and_the_last_takes_its_key(seed, key, every):
    """The history of the churn with the carriers watched: a group's term
    is one key per owner namespace while a member is resident and none
    after the last left; the binding's update moves as many as it takes
    off; a pod with terms of every kind brings the score's keys and takes
    them away. At every step the compiler is the one built anew."""
    cache, churn, _, _ = _standing(seed, key, every)
    snapshot = cache.update_snapshot()
    advanced = AffinityCompiler(snapshot, N_PAD)
    assert (advanced.came, advanced.gone) == (advanced.walked, 0)
    solo = [_anti_key("solo", key, ns) for ns in ("default", "other")]
    hard = repr((_term("db", key), "other", True))
    held, moved = {}, {}
    for k in range(14):
        churn.step(k)
        snapshot = cache.update_snapshot()
        changed = snapshot.changed_since(advanced.generation)
        assert advanced.advance(snapshot, N_PAD) == len(changed)
        assert advanced.reached == "delta"
        # what a delta looks at: the two lists of the nodes the log named
        assert advanced.walked == sum(
            len(snapshot.nodes[n].pods_with_required_anti_affinity)
            + len(snapshot.nodes[n].pods_with_affinity) for n in changed)
        _same_answers(advanced, AffinityCompiler(snapshot, N_PAD), snapshot)
        held[k] = [key_ in advanced.resident_anti for key_ in solo]
        moved[k] = (advanced.came, advanced.gone)
        if k in (4, 9):
            assert advanced.resident_score[hard][3] is True
            assert TPUBackend._ipa_score_relevant(
                _pending()[-2], advanced)
    assert held[1] == [False, False] and held[2] == [True, True]
    assert held[5] == [True, False]          # four left: one key emptied
    assert held[7] == [False, False]         # the last carrier went
    # step 2 binds five members, each on both of its node's lists
    assert moved[2] == (10, 0)
    # step 4: their update is one of each, and the rich pod came
    assert moved[4] == (12, 10)
    assert moved[5] == (0, 8)
    assert hard not in advanced.resident_score     # the rich pod left at 10
    if every:
        assert not advanced.resident_score


def test_a_delta_looks_at_the_changed_nodes_carriers_not_at_every_resident():
    """200 nodes, 2,000 residents that all carry a term: binding ten more
    is an advance over ten nodes' lists — tens of carriers, not 4,000."""
    rng = random.Random(5)
    cache = SchedulerCache()
    for i in range(200):
        cache.add_node(_node(i, rng.choice(ZONES)))
    churn = _Churn(cache, rng, HOSTNAME)
    for g in range(20):
        churn.bind_group(f"g{g}", 100)
    snapshot = cache.update_snapshot()
    n_pad = 256
    advanced = AffinityCompiler(snapshot, n_pad)
    assert (advanced.walked, advanced.came, advanced.gone) == (4000, 4000, 0)
    assert len(advanced.resident_anti) == 20
    churn.bind_group("g20", 10)
    snapshot = cache.update_snapshot()
    changed = snapshot.changed_since(advanced.generation)
    assert advanced.advance(snapshot, n_pad) == len(changed) == 10
    assert advanced.walked == sum(
        len(snapshot.nodes[n].pods_with_required_anti_affinity)
        + len(snapshot.nodes[n].pods_with_affinity) for n in changed)
    assert 20 <= advanced.walked < 400
    assert (advanced.came, advanced.gone) == (20, 0)
    assert len(advanced.resident_anti) == 21
    # the binding's update of those ten: as many came as went
    churn.confirm(churn.groups["g20"])
    snapshot = cache.update_snapshot()
    assert advanced.advance(snapshot, n_pad) == 10
    assert advanced.walked < 400
    assert (advanced.came, advanced.gone) == (20, 20)
    fresh = AffinityCompiler(snapshot, n_pad)
    assert set(advanced.resident_anti) == set(fresh.resident_anti)
    for key, (vec, _, _) in fresh.resident_anti.items():
        assert np.array_equal(advanced.resident_anti[key][0], vec)
    assert not advanced.resident_score


def test_an_empty_cluster_builds_and_advances():
    rng = random.Random(7)
    cache = _cluster(rng, nodes=6)
    snapshot = cache.update_snapshot()
    advanced = AffinityCompiler(snapshot, N_PAD)
    assert advanced.sigs.node_sig_count.shape == (N_PAD, 1)
    assert not advanced.counts_for({"matchLabels": {"app": "web"}},
                                   ("default", "other")).any()
    _Churn(cache, rng).bind(9)
    snapshot = cache.update_snapshot()
    assert 0 < advanced.advance(snapshot, N_PAD) <= 6
    _same_answers(advanced, AffinityCompiler(snapshot, N_PAD), snapshot)


def _relabel_node(cache):
    cache.update_node(_node(3, "z9"))


def _add_node(cache):
    cache.add_node(_node(len(cache.nodes), "z2"))


def _remove_node(cache):
    cache.remove_node("n7")


def _shorten_log(cache):
    # what SchedulerCache._refresh_clones does once the log outgrows its
    # bound: generations up to now fall out of the window
    cache._changed_log.clear()
    cache._log_floor = cache._generation


def _nothing(cache):
    pass


@pytest.mark.parametrize("event,full", [
    (_relabel_node, True),       # spec_seq
    (_add_node, True),           # set_epoch
    (_remove_node, True),        # set_epoch, positions shift
    (_shorten_log, True),        # the changed-log no longer reaches back
    (_nothing, False),           # the control: the same steps advance
], ids=["node_relabelled", "node_added", "node_removed", "log_too_short",
        "no_event"])
def test_what_the_handles_do_not_vouch_for_is_built_anew(event, full):
    cache, churn, fwk, batch = _standing(11)
    backend = _backend()
    _table(backend, cache.update_snapshot(), fwk, batch)
    churn.bind(4)
    _table(backend, cache.update_snapshot(), fwk, batch)
    assert _builds(backend) == (1, 1)
    churn.bind(4)
    event(cache)
    snapshot = cache.update_snapshot()
    got = _table(backend, snapshot, fwk, batch)
    assert _builds(backend) == ((2, 1) if full else (1, 2))
    planes = backend.metrics.spread_table_builds
    assert planes.value(planes="built") == (2 if full else 1)
    _same_table(got, _table(_backend(), snapshot, fwk, batch))
    _same_answers(backend._affinity,
                  AffinityCompiler(snapshot, backend._affinity.n_pad),
                  snapshot)
    # ... and from there it advances again
    churn.bind(4)
    snapshot = cache.update_snapshot()
    _same_table(_table(backend, snapshot, fwk, batch),
                _table(_backend(), snapshot, fwk, batch))
    assert _builds(backend) == ((2, 2) if full else (1, 3))


def test_a_namespace_relabel_is_built_anew():
    cache, churn, fwk, batch = _standing(12)
    backend = _backend()
    resolver = backend._ns_resolver = NamespaceResolver()
    _table(backend, cache.update_snapshot(), fwk, batch)
    churn.bind(4)
    _table(backend, cache.update_snapshot(), fwk, batch)
    assert _builds(backend) == (1, 1)
    resolver._epoch += 1            # what its informer handlers do
    resolver._memo.clear()
    # the same snapshot: the resolved namespace sets are what went stale
    _table(backend, cache.update_snapshot(), fwk, batch)
    assert _builds(backend) == (2, 1)
    churn.bind(4)
    snapshot = cache.update_snapshot()
    fresh = _backend()
    fresh._ns_resolver = resolver
    _same_table(_table(backend, snapshot, fwk, batch),
                _table(fresh, snapshot, fwk, batch))
    assert _builds(backend) == (2, 2)


def test_a_snapshot_without_handles_is_built_anew():
    cache, churn, fwk, batch = _standing(13)
    backend = _backend()
    made = cache.update_snapshot()
    _table(backend, made, fwk, batch)
    for k in range(1, 3):
        churn.bind(4)
        made = cache.update_snapshot()
        by_hand = Snapshot(list(made.nodes), made.generation)
        assert by_hand.changed_since is None and by_hand.set_epoch < 0
        got = _table(backend, by_hand, fwk, batch)
        assert _builds(backend) == (1 + k, 0)
        _same_table(got, _table(_backend(), made, fwk, batch))
        assert AffinityCompiler(by_hand, N_PAD).advance(made, N_PAD) is None


def _zone_spread_run(monkeypatch, delta: bool) -> list[dict]:
    """200 assign() calls of a zone-spread batch on 60 nodes in unequal
    zones, every placement assumed into the cache before the next."""
    if not delta:
        monkeypatch.setattr(AffinityCompiler, "advance",
                            lambda self, snapshot, n_pad: None)
    cache = SchedulerCache()
    zone_of = ["z1"] * 30 + ["z2"] * 20 + ["z3"] * 10
    for i, zone in enumerate(zone_of):
        cache.add_node(make_node(
            f"n{i}", labels={ZONE: zone},
            allocatable={"cpu": "8", "memory": "32Gi", "pods": "40"}))
    backend = _backend()
    fwk = Framework(build_plugins(), DEFAULT_SCORE_WEIGHTS)
    placed, confirm, assignments_before = [], [], {}
    for step in range(200):
        batch = [PodInfo(make_pod(
            f"s{step}-{j}", uid=f"s{step}-{j}", labels={"color": "blue"},
            requests={"cpu": "100m", "memory": "250Mi"},
            topology_spread_constraints=[{
                "maxSkew": 2, "topologyKey": ZONE,
                "whenUnsatisfiable": "DoNotSchedule",
                "labelSelector": {"matchLabels": {"color": "blue"}}}]))
            for j in range(5)]
        assignments, _ = backend.assign(batch, cache.update_snapshot(), fwk)
        for pi in confirm:
            # the binding's own event, as the scheduler's informer hands it
            # over: the assumed pod's object is replaced on its node
            cache.update_pod(PodInfo({**pi.pod, "spec": {
                **pi.pod["spec"], "nodeName": assignments_before[pi.key]}}))
        confirm = [pi for pi in batch if assignments[pi.key] is not None]
        assignments_before = assignments
        for pi in confirm:
            cache.assume_pod(pi, assignments[pi.key])
        placed.append(assignments)
    full, advanced = _builds(backend)
    assert (full, advanced) == ((1, 199) if delta else (200, 0))
    return placed


def test_two_hundred_batches_place_as_with_the_delta_path_off(monkeypatch):
    with monkeypatch.context() as m:
        without = _zone_spread_run(m, delta=False)
    with_delta = _zone_spread_run(monkeypatch, delta=True)
    assert with_delta == without
    assert sum(v is not None for a in with_delta for v in a.values()) > 900
