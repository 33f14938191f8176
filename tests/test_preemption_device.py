"""Device-side preemption victim proposal: parity vs the host analog.

SURVEY §7 phase 6 ("solve-with-victim-relaxation"): `solver.propose_victims`
replaces the per-preemptor host candidate search. These tests pin

- PARITY: for a seeded contention scenario, the device-proposed victim set
  matches the host `SelectVictimsOnNode` analog (`_select_victims`) —
  same victims, same minimal count — and the device choice carries the
  host cost-ordering optimum (`_WaveState.candidates`).
- DETERMINISM: identical seeded state → identical proposals.
- SPREADING: a wave's preemptors thread claims on device, so two
  preemptors do not stack on one node.
- The adaptive tuner's flagless picks stay within the documented envelope
  (BASELINE.md r6 "adaptive vs manual").
"""

from kubernetes_tpu.api.types import make_node, make_pod
from kubernetes_tpu.ops.backend import AdaptiveTuner, TPUBackend
from kubernetes_tpu.scheduler.framework import CycleState, Framework
from kubernetes_tpu.scheduler.plugins.defaultpreemption import (
    DefaultPreemption,
    _WaveState,
)
from kubernetes_tpu.scheduler.plugins.noderesources import NodeResourcesFit
from kubernetes_tpu.scheduler.types import NodeInfo, PodInfo, Snapshot


def ni(name, cpu="4", pods=()):
    node = NodeInfo(make_node(
        name, allocatable={"cpu": cpu, "memory": "16Gi", "pods": "32"}))
    for p in pods:
        node.add_pod(p)
    return node


def pp(name, cpu="1", priority=0):
    return PodInfo(make_pod(name, requests={"cpu": cpu, "memory": "1Gi"},
                            priority=priority))


def contention_snapshot():
    """3 full nodes; victim priorities ascend differently per node so the
    reference cost ordering (max prio → prio sum → count) is exercised."""
    nodes = [
        ni("n0", pods=[pp("a0", priority=50), pp("a1", priority=60),
                       pp("a2", priority=70), pp("a3", priority=80)]),
        ni("n1", pods=[pp("b0", priority=10), pp("b1", priority=20),
                       pp("b2", priority=90), pp("b3", priority=95)]),
        ni("n2", pods=[pp("c0", priority=30), pp("c1", priority=40),
                       pp("c2", priority=45), pp("c3", priority=85)]),
    ]
    return Snapshot(nodes, generation=1)


def make_plugin(snapshot, seed=0):
    fwk = Framework([NodeResourcesFit()], {"NodeResourcesFit": 1})
    evictions = []
    plug = DefaultPreemption(
        args={"seed": seed}, framework=fwk,
        evict=lambda pod, victims, node: evictions.append(
            (pod.key, tuple(victims), node)))
    return plug, evictions


class TestDeviceHostParity:
    def test_primed_matches_host_minimal_victims(self):
        snap = contention_snapshot()
        preemptor = pp("hi", cpu="1", priority=1000)
        plug, _ = make_plugin(snap)

        # Host analogs computed BEFORE any claim mutates shared state.
        ref_wave = _WaveState(snap, set(), {})
        ranked = ref_wave.candidates(preemptor, set())
        best_n, best_count = ranked[0]
        host_cost = DefaultPreemption._cost_of(ref_wave, ranked[0])
        scan_victims = plug._select_victims(
            CycleState(), preemptor, snap.nodes[best_n])

        plug.prime_wave([preemptor], snap, {})
        assert preemptor.key in plug._primed
        _, dev_n, dev_count = plug._primed[preemptor.key]
        wave = plug._wave
        # Device pick carries the host cost-ordering optimum. (The node
        # itself may differ only under exact cost ties; this scenario has
        # none — assert full identity.)
        assert DefaultPreemption._cost_of(
            wave, (dev_n, dev_count)) == host_cost
        assert (dev_n, dev_count) == (best_n, best_count)
        # Same victim SET and same minimal count as the host
        # SelectVictimsOnNode analog (homogeneous requests, so the
        # minimal ascending-priority prefix IS the reprieve result).
        dev_victims = {v.key for v in wave.victims[dev_n][:dev_count]}
        assert dev_victims == {v.key for v in scan_victims}
        assert dev_count == len(scan_victims)

    def test_post_filter_commits_primed_proposal(self):
        snap = contention_snapshot()
        preemptor = pp("hi", cpu="1", priority=1000)
        plug, evictions = make_plugin(snap)
        plug.prime_wave([preemptor], snap, {})
        primed = dict(plug._primed)
        node, st = plug.post_filter(CycleState(), preemptor, snap, {})
        assert st.is_success()
        _, dev_n, dev_count = primed[preemptor.key]
        assert node == snap.nodes[dev_n].name
        assert len(evictions) == 1
        assert len(evictions[0][1]) == dev_count
        # the proposal was consumed, not left to go stale
        assert preemptor.key not in plug._primed

    def test_deterministic_tiebreak(self):
        results = []
        for _ in range(2):
            snap = contention_snapshot()
            preemptor = pp("hi", cpu="1", priority=1000)
            plug, _ = make_plugin(snap, seed=7)
            plug.prime_wave([preemptor], snap, {})
            results.append(plug._primed[preemptor.key][1:])
        assert results[0] == results[1]

    def test_wave_spreads_across_equal_nodes(self):
        # Two identical single-victim nodes, two preemptors in ONE wave:
        # in-scan claim threading consumes the first choice's only victim
        # (and charges the preemptor), so the second preemptor MUST land
        # on the other node — no host round trip between them.
        nodes = [ni("n0", cpu="1", pods=[pp("a0", priority=1)]),
                 ni("n1", cpu="1", pods=[pp("b0", priority=1)])]
        snap = Snapshot(nodes, generation=1)
        p1 = pp("hi-1", cpu="1", priority=100)
        p2 = pp("hi-2", cpu="1", priority=100)
        plug, _ = make_plugin(snap)
        plug.prime_wave([p1, p2], snap, {})
        assert {plug._primed[p1.key][1],
                plug._primed[p2.key][1]} == {0, 1}

    def test_byte_quantity_resources_do_not_overflow(self):
        # Memory is tracked in BYTES (int64 on host): the device scan is
        # int32, so victim proposal must quantize conservatively instead
        # of clamping/overflowing. 224Gi used of 256Gi, 32Gi freed by one
        # victim, preemptor wants 32Gi → exactly one victim suffices.
        victim = PodInfo(make_pod(
            "big-victim", requests={"cpu": "1", "memory": "32Gi"},
            priority=1))
        filler = PodInfo(make_pod(
            "big-filler", requests={"cpu": "1", "memory": "192Gi"},
            priority=2000))
        node = NodeInfo(make_node("m0", allocatable={
            "cpu": "8", "memory": "256Gi", "pods": "16"}))
        node.add_pod(victim)
        node.add_pod(filler)
        snap = Snapshot([node], generation=1)
        preemptor = PodInfo(make_pod(
            "hi-mem", requests={"cpu": "1", "memory": "32Gi"},
            priority=1000))
        plug, evictions = make_plugin(snap)
        plug.prime_wave([preemptor], snap, {})
        assert preemptor.key in plug._primed
        _, n, count = plug._primed[preemptor.key]
        assert (n, count) == (0, 1)
        node_name, st = plug.post_filter(CycleState(), preemptor, snap, {})
        assert st.is_success() and node_name == "m0"
        assert evictions[0][1] == ("default/big-victim",)

    def test_priority_threshold_and_banned(self):
        snap = contention_snapshot()
        plug, _ = make_plugin(snap)
        # Preemptor below every resident priority: nothing to propose.
        low = pp("low", cpu="1", priority=5)
        plug.prime_wave([low], snap, {})
        assert low.key not in plug._primed

    def test_in_flight_guard_renominates_without_reeviction(self):
        snap = contention_snapshot()
        preemptor = pp("hi", cpu="1", priority=1000)
        plug, evictions = make_plugin(snap)
        node, st = plug.post_filter(CycleState(), preemptor, snap, {})
        assert st.is_success() and len(evictions) == 1
        # Victims are still resident (no informer ran the deletes): a
        # retry must re-nominate the SAME node with NO second eviction.
        node2, st2 = plug.post_filter(CycleState(), preemptor, snap, {})
        assert st2.is_success()
        assert node2 == node
        assert len(evictions) == 1


class TestAdaptiveTunerEnvelope:
    def test_flagless_backend_decides_after_warmup(self):
        backend = TPUBackend()          # flagless: tuner owns the depth
        assert backend.max_batch == 1024 and backend.pipeline_depth == 4
        t = backend._tuner
        assert t.decide() is None       # warmup: no decision yet
        for _ in range(t.WARMUP_CHUNKS):
            t.observe_chunk()
        assert t.decide() == 2
        assert t.probe() > 0 and t.latency_s is not None

    def test_explicit_chunk_is_an_override(self):
        assert TPUBackend(max_batch=8).max_batch == 8


class TestWorkloadResultEventDrops:
    def test_as_dict_reports_drop_rate(self):
        from kubernetes_tpu.perf.scheduler_perf import WorkloadResult
        r = WorkloadResult()
        r.events_emitted_total = 10000
        r.events_dropped_total = 8000
        d = r.as_dict()
        assert d["events_dropped_total"] == 8000
        assert d["events_dropped_pct"] == 80.0
