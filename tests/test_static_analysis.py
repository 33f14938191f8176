"""ktpu-lint (kubernetes_tpu/analysis): seeded-violation fixtures per
pass, baseline round-trip, CLI exit codes, and the tier-1 gate.

The fixtures are the pass's own differential tests: each plants one
violation per finding code in a temp tree shaped like the repo and
asserts the pass catches exactly it. The gate then asserts the REAL
tree is clean (zero unsuppressed findings against the checked-in
baseline) — the invariant every future PR inherits.
"""

import json
import os
import textwrap

import pytest

from kubernetes_tpu.analysis import run_all
from kubernetes_tpu.analysis.engine import (
    Module,
    apply_baseline,
    load_baseline,
)
from kubernetes_tpu.analysis import (
    flags_pass,
    jit_purity,
    locks,
    metrics_lint,
)


def _module(tmp_path, rel, source) -> Module:
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    return Module.load(str(path), str(tmp_path))


# ---------------------------------------------------------------------------
# pass 1: jit-purity
# ---------------------------------------------------------------------------

class TestJitPurity:
    FIXTURE = """
        import time
        import jax
        import jax.numpy as jnp
        import numpy as np
        from functools import partial
        from jax import lax


        @jax.jit
        def bad(x):
            v = float(jnp.max(x))          # JP103: cast concretizes
            y = np.asarray(x)              # JP101: host materialization
            t = time.time()                # JP102: frozen at trace time
            if jnp.any(x > 0):             # JP103: python branch
                return x
            return helper(x)

        def helper(x):
            return x.item()                # JP101, via the call graph

        def scan_user(xs):
            def step(carry, x):
                print(carry)               # JP102 inside a scan body
                return carry, x
            return lax.scan(step, 0, xs)

        def host_driver(x):
            # NOT jit-reachable: no decorator, nothing hands it to a
            # trace wrapper — host syncs here are sanctioned.
            return np.asarray(x)
    """

    def test_seeded_violations_caught(self, tmp_path):
        mod = _module(tmp_path, "kubernetes_tpu/ops/solver.py",
                      self.FIXTURE)
        found = jit_purity.run([mod])
        codes = sorted((f.code, f.symbol.split(":")[0]) for f in found)
        assert ("JP101", "bad") in codes            # np.asarray
        assert ("JP101", "helper") in codes         # .item() via graph
        assert ("JP102", "bad") in codes            # time.time
        assert ("JP102", "scan_user.step") in codes  # print in scan body
        jp103 = [s for c, s in codes if c == "JP103"]
        assert "bad" in jp103                       # float() and/or if
        assert sum(1 for c, s in codes if s == "bad" and c == "JP103") == 2

    def test_host_driver_not_flagged(self, tmp_path):
        mod = _module(tmp_path, "kubernetes_tpu/ops/solver.py",
                      self.FIXTURE)
        found = jit_purity.run([mod])
        assert not any(f.symbol.startswith("host_driver") for f in found)

    def test_clean_kernel_passes(self, tmp_path):
        mod = _module(tmp_path, "kubernetes_tpu/ops/kernels.py", """
            import jax
            import jax.numpy as jnp

            @jax.jit
            def clean(x, y):
                m = jnp.where(x > 0, x, y)
                n = int(x.shape[0])   # shape math is static — legal
                return m * n
        """)
        assert jit_purity.run([mod]) == []


# ---------------------------------------------------------------------------
# pass 2: lock discipline
# ---------------------------------------------------------------------------

class TestLockDiscipline:
    FIXTURE = """
        import asyncio
        import threading

        import numpy as np


        class Inverted:
            def __init__(self):
                self._lock_a = threading.Lock()
                self._lock_b = threading.Lock()

            def ab(self):
                with self._lock_a:
                    with self._lock_b:
                        return 1

            def ba(self):
                with self._lock_b:
                    with self._lock_a:   # LK201: closes the cycle
                        return 2


        class HeldAcross:
            def __init__(self):
                self._lock = threading.Lock()
                self._values = {}

            def fetch(self):
                with self._lock:
                    return np.asarray([1.0])      # LK203

            async def sleepy(self):
                with self._lock:
                    await asyncio.sleep(0.1)      # LK202

            def send(self, sock):
                with self._lock:
                    sock.sendall(b"x")            # LK204

            def rotate(self, path):
                with self._lock:
                    with open(path, "a") as f:    # LK206
                        f.write("x")

            def shuffle(self, path):
                with self._lock:
                    import os
                    os.replace(path, path + ".1")  # LK206

            def one_statement(self, path):
                with self._lock, open(path, "a") as f:  # LK206 too
                    f.write("x")

            def rotate_outside(self, path):
                segment = None
                with self._lock:
                    segment = dict(self._values)
                with open(path, "a") as f:        # clean: lock released
                    f.write(str(segment))

            def write(self, k):
                with self._lock:
                    self._values[k] = 1

            def render(self):
                return sorted(self._values.items())   # LK205


        class CondOk:
            def __init__(self):
                self._cond = asyncio.Condition()
                self._items = []

            async def wait(self):
                async with self._cond:
                    await self._cond.wait()       # sanctioned
                    await asyncio.wait_for(self._cond.wait_for(
                        lambda: self._items), 1.0)  # sanctioned, wrapped
                    return list(self._items)


        class ProcSpawner:
            def __init__(self):
                self._lock = threading.Lock()
                self._proc = None
                self._workers = []

            def spawn(self):
                import subprocess
                with self._lock:
                    subprocess.run(["true"])      # LK207: exec under lock

            def boot(self):
                import multiprocessing
                with self._lock:
                    self._proc = multiprocessing.Process(  # LK207
                        target=print)
                    self._proc.start()            # LK207: proc receiver

            def reap(self):
                with self._lock:
                    self._workers[0].join()       # LK207: subscripted

            def tag(self, parts):
                with self._lock:
                    return ",".join(parts)        # clean: not a process

            def reap_outside(self, proc):
                with self._lock:
                    alive = bool(self._proc)
                proc.join()                       # clean: lock released
                return alive
    """

    def _run(self, tmp_path):
        mod = _module(tmp_path, "kubernetes_tpu/store/fixture.py",
                      self.FIXTURE)
        return locks.run([mod])

    def test_cycle_detected(self, tmp_path):
        found = self._run(tmp_path)
        assert any(f.code == "LK201" for f in found)

    def test_held_across_hazards(self, tmp_path):
        codes = {f.code: f for f in self._run(tmp_path)}
        assert "LK202" in codes     # await under a threading lock
        assert "LK203" in codes     # device fetch under a lock
        assert "LK204" in codes     # wire send under a lock

    def test_file_io_under_lock(self, tmp_path):
        """LK206 (ISSUE 15, the audit sink workers): open()/os.replace
        under a held lock flagged — in `with open(...)` context-expr
        form and the bare-call form — while I/O after the lock is
        released stays clean."""
        found = self._run(tmp_path)
        lk206 = [f for f in found if f.code == "LK206"]
        assert {f.symbol for f in lk206} == {
            "HeldAcross.rotate:open", "HeldAcross.shuffle:os.replace",
            "HeldAcross.one_statement:open"}
        assert not any("rotate_outside" in f.symbol for f in found)

    def test_unlocked_iteration_of_guarded_state(self, tmp_path):
        found = self._run(tmp_path)
        lk205 = [f for f in found if f.code == "LK205"]
        assert len(lk205) == 1
        assert "_values" in lk205[0].symbol

    def test_condition_wait_is_sanctioned(self, tmp_path):
        found = self._run(tmp_path)
        assert not any("CondOk" in f.symbol for f in found)

    def test_process_spawn_join_under_lock(self, tmp_path):
        """LK207 (ISSUE r22, the multiproc supervisor): spawning an OS
        process or joining one while holding a lock is flagged —
        interpreter boot is ~100s of ms, a join unbounded — while
        `",".join(...)` under a lock and a process join after release
        stay clean."""
        found = self._run(tmp_path)
        lk207 = [f for f in found if f.code == "LK207"]
        assert {f.symbol.split(":")[0] for f in lk207} == {
            "ProcSpawner.spawn", "ProcSpawner.boot", "ProcSpawner.reap"}
        assert len(lk207) == 4          # boot: Process(...) AND .start()
        assert not any(f.symbol.startswith(("ProcSpawner.tag",
                                            "ProcSpawner.reap_outside"))
                       for f in found)


# ---------------------------------------------------------------------------
# pass 3: flag registry
# ---------------------------------------------------------------------------

class TestFlagRegistry:
    def test_unrouted_read_and_unknown_flag(self, tmp_path):
        mod = _module(tmp_path, "kubernetes_tpu/ops/fixture.py", """
            import os

            def bad():
                a = os.environ.get("KTPU_SERVING", "1")     # FL301
                b = os.environ["KTPU_BOGUS_FLAG"]           # FL301+FL302
                c = os.getenv("KTPU_CLASS_PAD")             # FL301
                os.environ["KTPU_SERVING"] = "0"            # write: legal
                os.environ.pop("KTPU_SERVING", None)        # write: legal
                return a, b, c
        """)
        found = flags_pass.run([mod], root=str(tmp_path))
        fl301 = sorted(f.symbol for f in found if f.code == "FL301")
        assert fl301 == ["KTPU_BOGUS_FLAG", "KTPU_CLASS_PAD",
                         "KTPU_SERVING"]
        assert [f.symbol for f in found if f.code == "FL302"] \
            == ["KTPU_BOGUS_FLAG"]

    def test_registry_reads_are_exempt(self, tmp_path):
        mod = _module(tmp_path, "kubernetes_tpu/utils/flags.py", """
            import os

            def read(name):
                return os.environ.get(name) or os.environ.get("KTPU_X")
        """)
        found = flags_pass.run([mod], root=str(tmp_path))
        assert not any(f.code == "FL301" for f in found)

    def test_registry_contract(self):
        """Every flag: registered, documented, expected default — and
        NAMED here, which is what the FL304 'every flag has a test'
        check greps for: KTPU_SERVING,
        KTPU_WAVEFRONT, KTPU_WAVE_WIDTH, KTPU_SOLVE_MODE,
        KTPU_SINKHORN_ITERS, KTPU_SINKHORN_TEMP, KTPU_DESCHEDULER,
        KTPU_DESCHEDULER_BUDGET, KTPU_TOPOLOGY, KTPU_MESH_SHAPE,
        KTPU_WATCH_CACHE,
        KTPU_POLICY_INDEX, KTPU_SHARDS,
        KTPU_SHARD_THRESHOLD, KTPU_CLASS_PAD, KTPU_PIPELINE_DEPTH,
        KTPU_SHORTLIST_K, KTPU_BLOCK_WIDTH,
        KTPU_ADMISSION_WINDOW,
        KTPU_TRACE_THRESHOLD_MS, KTPU_DATA_DIR, KTPU_LOCK_CHECK,
        KTPU_DEBUG_FREEZE, KTPU_TEST_PLATFORM, KTPU_PROCESSES,
        KTPU_WAL, KTPU_WAL_FSYNC, KTPU_LEASE_DURATION."""
        from kubernetes_tpu.utils import flags
        expected_defaults = {
            "KTPU_SERVING": True,
            "KTPU_WAVEFRONT": True,
            "KTPU_WAVE_WIDTH": None,
            "KTPU_SOLVE_MODE": "auto",
            "KTPU_SINKHORN_ITERS": 24,
            "KTPU_SINKHORN_TEMP": 0.05,
            "KTPU_DESCHEDULER": False,
            "KTPU_DESCHEDULER_BUDGET": 8,
            "KTPU_TOPOLOGY": True,
            "KTPU_MESH_SHAPE": "auto",
            "KTPU_WATCH_CACHE": True,
            "KTPU_POLICY_INDEX": True,
            "KTPU_SHARDS": None,
            "KTPU_SHARD_THRESHOLD": 100_000,
            "KTPU_PROCESSES": None,
            "KTPU_WAL": True,
            "KTPU_WAL_FSYNC": "batch",
            "KTPU_LEASE_DURATION": 15.0,
            "KTPU_CLASS_PAD": 31,
            "KTPU_PIPELINE_DEPTH": None,
            "KTPU_SHORTLIST_K": None,
            "KTPU_BLOCK_WIDTH": None,
            "KTPU_ADMISSION_WINDOW": None,
            "KTPU_TRACE_THRESHOLD_MS": None,
            "KTPU_DATA_DIR": None,
            "KTPU_LOCK_CHECK": False,
            "KTPU_DEBUG_FREEZE": False,
            "KTPU_TEST_PLATFORM": "cpu",
        }
        assert set(flags.FLAGS) == set(expected_defaults)
        for name, default in expected_defaults.items():
            assert flags.FLAGS[name].default == default, name
            assert flags.FLAGS[name].doc.strip(), name
        kills = {n for n, f in flags.FLAGS.items() if f.kill_switch}
        assert kills == {"KTPU_SERVING",
                         "KTPU_WAVEFRONT",
                         "KTPU_SOLVE_MODE", "KTPU_TOPOLOGY",
                         "KTPU_WATCH_CACHE",
                         "KTPU_POLICY_INDEX", "KTPU_SHARDS",
                         "KTPU_PROCESSES", "KTPU_WAL"}

    def test_parse_behaviors(self, monkeypatch):
        from kubernetes_tpu.utils import flags
        for off in ("0", "false", "False", "FALSE", "off", "no"):
            monkeypatch.setenv("KTPU_SERVING", off)
            assert flags.get("KTPU_SERVING") is False, off
        monkeypatch.setenv("KTPU_SERVING", "1")
        assert flags.get("KTPU_SERVING") is True
        monkeypatch.delenv("KTPU_SERVING")
        assert flags.get("KTPU_SERVING") is True
        # malformed values degrade to the default, never crash
        monkeypatch.setenv("KTPU_CLASS_PAD", "garbage")
        assert flags.get("KTPU_CLASS_PAD") == 31
        monkeypatch.setenv("KTPU_TRACE_THRESHOLD_MS", "not-a-float")
        assert flags.get("KTPU_TRACE_THRESHOLD_MS") is None
        # ms windows clamp negative to 0
        monkeypatch.setenv("KTPU_ADMISSION_WINDOW", "-5")
        assert flags.get("KTPU_ADMISSION_WINDOW") == 0.0
        with pytest.raises(KeyError):
            flags.get("KTPU_NOT_REGISTERED")

    def test_scoped_set_restores(self, monkeypatch):
        from kubernetes_tpu.utils import flags
        monkeypatch.delenv("KTPU_SHARDS", raising=False)
        with flags.scoped_set("KTPU_SHARDS", 4):
            assert flags.get("KTPU_SHARDS") == 4
        assert flags.get("KTPU_SHARDS") is None
        monkeypatch.setenv("KTPU_SHARDS", "2")
        with flags.scoped_set("KTPU_SHARDS", 8):
            assert flags.get("KTPU_SHARDS") == 8
        assert flags.get("KTPU_SHARDS") == 2

    def test_readme_table_in_sync(self):
        """FL305 end to end: the checked-in README matches the render."""
        from kubernetes_tpu.analysis.engine import repo_root
        found = flags_pass.run([], root=repo_root())
        assert not any(f.code == "FL305" for f in found), \
            [f.message for f in found]


# ---------------------------------------------------------------------------
# pass 4: metrics lint
# ---------------------------------------------------------------------------

class TestMetricsLint:
    def test_seeded_violations_caught(self, tmp_path):
        mod = _module(tmp_path, "kubernetes_tpu/metrics/registry.py", """
            class Metrics:
                def __init__(self, r):
                    self.a = r.counter("foo_count", "no _total")
                    self.b = r.gauge("window_ms", "bad unit")
                    self.c = r.histogram("req_duration", "no unit")
                    self.d = r.counter("x_total", "hot label",
                                       labels=("pod",))
                    self.e = r.gauge("ok_gauge_total", "fake counter")
                    self.f = r.histogram(
                        "apiserver_request_duration_seconds", "clean",
                        labels=("verb", "resource", "code"))
        """)
        by_code = {}
        for f in metrics_lint.run([mod]):
            by_code.setdefault(f.code, []).append(f.symbol)
        assert by_code.get("MT402") == ["foo_count"]
        assert by_code.get("MT404") == ["window_ms"]
        assert by_code.get("MT406") == ["req_duration"]
        assert by_code.get("MT405") == ["x_total:pod"]
        assert by_code.get("MT403") == ["ok_gauge_total"]
        clean = "apiserver_request_duration_seconds"
        assert not any(clean in syms
                       for syms in by_code.values() for syms in [syms]
                       if any(clean == s.split(":")[0] for s in syms))

    def test_registrations_outside_registry_scanned(self, tmp_path):
        """ISSUE 15 widened the scan: a counter constructed in
        policy/audit.py (the sink counters) is linted like one in
        metrics/registry.py — a bad name anywhere fails."""
        mod = _module(tmp_path, "kubernetes_tpu/policy/audit.py", """
            class Sink:
                def __init__(self, r):
                    self.drops = r.counter("audit_dropped", "no _total")
        """)
        found = metrics_lint.run([mod])
        assert [f.code for f in found] == ["MT402"]

    def test_real_sink_counters_visible_to_pass(self):
        """Non-vacuity: the pass actually reaches the live audit/vap
        registrations (policy_index_*, audit_webhook_*, rotation) —
        and finds them clean."""
        from kubernetes_tpu.analysis.engine import load_modules
        mods = [m for m in load_modules()
                if m.rel in ("kubernetes_tpu/policy/audit.py",
                             "kubernetes_tpu/policy/vap.py")]
        names = {name for m in mods
                 for _k, name, _l, _ln in metrics_lint._registrations(m)}
        assert {"policy_index_hits_total",
                "policy_index_residue_scans_total",
                "policy_index_rebuilds_total",
                "audit_log_rotations_total",
                "audit_webhook_batches_total",
                "audit_webhook_retries_total"} <= names
        assert metrics_lint.run(mods) == []

    def test_block_index_counters_visible_to_pass(self):
        """Non-vacuity for the ISSUE 20 block-index metrics: the lint
        pass actually reaches the live registrations (the scanned /
        pruned counters the KTPU_BLOCK_WIDTH flag gates) — and finds
        them clean. A rename
        that dropped the _total/_seconds suffixes, or a registration
        moved out of the scanned set, fails here instead of silently
        exempting the new names."""
        from kubernetes_tpu.analysis.engine import load_modules
        mods = [m for m in load_modules()
                if m.rel == "kubernetes_tpu/metrics/registry.py"]
        names = {name for m in mods
                 for _k, name, _l, _ln in metrics_lint._registrations(m)}
        assert {"scheduler_tpu_solver_blocks_scanned_total",
                "scheduler_tpu_solver_blocks_pruned_total"} <= names
        assert metrics_lint.run(mods) == []

    def test_real_registry_would_catch_ms_gauge(self, tmp_path):
        """The r17 defect as a regression fixture: a `_ms` gauge in the
        registry is exactly what the pass exists to reject."""
        mod = _module(tmp_path, "kubernetes_tpu/metrics/registry.py", """
            def build(r):
                return r.gauge(
                    "scheduler_admission_window_ms",
                    "Serving admission coalesce window")
        """)
        found = metrics_lint.run([mod])
        assert [f.code for f in found] == ["MT404"]


# ---------------------------------------------------------------------------
# baseline + CLI + the tier-1 gate
# ---------------------------------------------------------------------------

class TestBaseline:
    def test_round_trip(self, tmp_path):
        mod = _module(tmp_path, "kubernetes_tpu/ops/fixture.py", """
            import os
            def bad():
                return os.environ.get("KTPU_SERVING")
        """)
        found = flags_pass.run([mod], root=str(tmp_path))
        assert len(found) == 1
        baseline_file = tmp_path / "baseline.json"
        baseline_file.write_text(json.dumps({
            "version": 1,
            "suppressions": [{"key": found[0].key,
                              "reason": "fixture: deliberate"}],
        }))
        baseline = load_baseline(str(baseline_file))
        unsup, sup, stale = apply_baseline(found, baseline)
        assert unsup == [] and len(sup) == 1 and stale == []

    def test_stale_suppressions_reported(self):
        unsup, sup, stale = apply_baseline(
            [], {"flag-registry:FL301:gone.py:KTPU_X": "obsolete"})
        assert stale == ["flag-registry:FL301:gone.py:KTPU_X"]

    def test_keys_are_line_stable(self, tmp_path):
        src = """
            import os
            def bad():
                return os.environ.get("KTPU_SERVING")
        """
        m1 = _module(tmp_path, "kubernetes_tpu/ops/fixture.py", src)
        k1 = flags_pass.run([m1], root=str(tmp_path))[0].key
        m2 = _module(tmp_path, "kubernetes_tpu/ops/fixture.py",
                     "\n\n# moved down\n" + textwrap.dedent(src))
        k2 = flags_pass.run([m2], root=str(tmp_path))[0].key
        assert k1 == k2


class TestCLI:
    def test_exit_zero_on_clean_tree(self, capsys):
        from kubernetes_tpu.analysis import main
        assert main([]) == 0
        out = capsys.readouterr().out
        assert "0 unsuppressed" in out

    def test_exit_two_on_internal_error(self, tmp_path, capsys):
        from kubernetes_tpu.analysis import main
        broken = tmp_path / "broken.json"
        broken.write_text("{not json")
        assert main(["--baseline", str(broken)]) == 2

    def test_json_output_schema(self, capsys):
        from kubernetes_tpu.analysis import main
        assert main(["--json"]) == 0
        data = json.loads(capsys.readouterr().out)
        assert set(data) == {"findings", "suppressed",
                             "stale_suppressions", "per_pass"}
        assert set(data["per_pass"]) == {
            "jit-purity", "lock-discipline", "flag-registry",
            "metrics-lint"}


class TestTierOneGate:
    def test_tree_is_clean(self):
        """THE gate: zero unsuppressed findings on the real tree. A new
        finding either gets fixed or goes into analysis/baseline.json
        with a reason string — never ignored."""
        unsup, _sup, stale, per_pass = run_all()
        assert unsup == [], "\n".join(
            f"{f.path}:{f.line}: {f.code} {f.message}" for f in unsup)
        # triage rot guard: the checked-in baseline matches real findings
        assert stale == [], stale

    def test_jit_purity_walked_the_solve_path(self):
        """Anti-vacuity: the purity pass must actually discover the
        solver/kernel entry points — a refactor that silently empties
        the reachable set would make the pass pass forever."""
        from kubernetes_tpu.analysis.engine import (
            FunctionIndex,
            load_modules,
        )
        mods = load_modules()
        entry_mods = [m for m in mods
                      if m.rel.endswith(
                          jit_purity.ENTRY_MODULE_SUFFIXES)]
        indices = {m.rel: FunctionIndex(m) for m in entry_mods}
        entry_map = {rel: jit_purity._entry_functions(idx)
                     for rel, idx in indices.items()}
        assert entry_map["kubernetes_tpu/ops/solver.py"], \
            "no jit entries found in ops/solver.py"
        # The r18 wavefront scans are new jit entry points on the
        # hottest path — discovery must see them as entries...
        solver_entries = entry_map["kubernetes_tpu/ops/solver.py"]
        for fn in ("greedy_assign_rescoring_wave",
                   "multistart_greedy_assign_wave",
                   "greedy_assign_rescoring_spread_wave",
                   "greedy_assign_rescoring_shortlist_wave",
                   "multistart_greedy_assign_shortlist_wave"):
            assert fn in solver_entries, \
                f"wavefront entry {fn} not discovered"
        reach = jit_purity._reachable(indices, entry_map)
        rels = {rel for rel, _ in reach}
        assert "kubernetes_tpu/ops/kernels.py" in rels, \
            "call graph no longer reaches the kernels"
        # ...and the walk must reach the wave-step/replay bodies (new
        # lax.scan / fori_loop callees nested under the entries) — an
        # emptied reachable set here would let host syncs into the wave
        # bodies pass the gate forever.
        solver_reach = {qn for rel, qn in reach
                        if rel == "kubernetes_tpu/ops/solver.py"}
        for qn in ("_rescoring_wave_scan.wave_step",
                   "_rescoring_wave_scan.wave_step.slow.body",
                   "_shortlist_wave_scan.wave_step",
                   "greedy_assign_rescoring_spread_wave.wave_step",
                   "_wave_spec_picks", "_wave_conflicts"):
            assert qn in solver_reach, \
                f"purity walk no longer reaches {qn}"
        # The r20 optimal mode adds the Sinkhorn iteration body (a
        # fori_loop callee under the jitted plan) — same anti-vacuity
        # stake: a host sync inside the transport loop must stay visible
        # to the gate.
        assert "sinkhorn_plan" in solver_entries, \
            "sinkhorn_plan not discovered as a jit entry"
        assert "sinkhorn_plan.step" in solver_reach, \
            "purity walk no longer reaches the Sinkhorn iteration body"
        # The resident planes' row scatter (the one jitted body outside
        # ops/ that every batch assign runs) is an entry too.
        assert "resident_row_scatter.body" in \
            entry_map["kubernetes_tpu/serving/resident.py"]
        # ISSUE 20's block-sparse prefilter: the lax.cond branch bodies
        # (exact accept vs whole-chunk full-width fallback) are named
        # functions passed to a trace wrapper — entry points in their
        # own right — and the walk must reach the prefilter plus every
        # aggregate/bound/gather kernel it composes. A host sync inside
        # any of these runs on the hottest large-N path.
        for fn in ("block_bound_prefilter._block_exact",
                   "block_bound_prefilter._block_fallback_full"):
            assert fn in solver_entries, \
                f"block cond branch {fn} not discovered as an entry"
        for qn in ("block_bound_prefilter",
                   "block_bound_prefilter._block_exact",
                   "block_bound_prefilter._block_fallback_full"):
            assert qn in solver_reach, \
                f"purity walk no longer reaches {qn}"
        kernels_reach = {qn for rel, qn in reach
                         if rel == "kubernetes_tpu/ops/kernels.py"}
        for qn in ("block_capacity_aggregates", "block_feasible_stat",
                   "block_score_upper_bound", "gathered_start_scores",
                   "gathered_start_scores.one", "_block_fold"):
            assert qn in kernels_reach, \
                f"purity walk no longer reaches block kernel {qn}"
        assert len(reach) >= 20
