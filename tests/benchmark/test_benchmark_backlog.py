"""The `backlog` traffic kind (benchmark/kinds/backlog.py,
benchmark/traffic/backlog.json) and its cell `sched-perf-5k.backlog`, on
the CPU at a size of hundreds: the real files run by the unchanged
harness against the program with its hold/release seam — nothing binds
while the scheduler is held, every round releases a full backlog, the
rate is bound pods over the summed drain stretches — and against the
control's scheduler, which has no such seam: the kind raises at once.
"""

import io
import json
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.lib.cluster import Cluster  # noqa: E402
from benchmark.lib.control import control_cluster  # noqa: E402
from benchmark.lib.harness import run_cell  # noqa: E402
from benchmark.lib.manifest import Manifest  # noqa: E402

CELL = "sched-perf-5k.backlog"
WAVE = 600          # past the serving tier's fast-path cap: the batch path


def _small_tree() -> Manifest:
    tree = Manifest()
    config = dict(tree.config(tree.cell(CELL)),
                  nodes=200, init_pods=20, wave_pods=WAVE)
    tree.config = lambda cell: config
    mix = dict(tree.traffic(tree.cell(CELL)), barrier_seconds=30,
               trace_seconds=1.0, warm_min_chunks=2)
    tree.traffic = lambda cell: mix
    return tree


def _run(tree, trace=False, seconds=1.0, **kw):
    out, err = io.StringIO(), io.StringIO()
    rc = run_cell(CELL, 2**31 + 3233, seconds, trace, manifest=tree,
                  require_chip=False, stdout=out, stderr=err, **kw)
    return rc, json.loads(out.getvalue().strip().splitlines()[-1]), \
        err.getvalue()


def _window(err: str) -> dict:
    line = next(ln for ln in err.splitlines()
                if ln.startswith("bench: window"))
    return json.loads(line[len("bench: window "):])


class Watched(Cluster):
    """The program's cluster, with what the client had seen bound and
    what the scheduler had queued noted at every hold and release."""

    holds: list

    def build_scheduler(self):
        sched = super().build_scheduler()
        hold, release = sched.hold, sched.release
        cluster = self
        type(self).holds = []

        async def noted_hold():
            await hold()
            cluster.holds.append({"bound_at_hold": len(cluster.bound)})

        async def noted_release():
            cluster.holds[-1].update(
                bound_at_release=len(cluster.bound),
                queued=sched.queue.stats()["active"],
                in_flight=sched.queue.stats()["in_flight"])
            await release()
        sched.hold, sched.release = noted_hold, noted_release
        return sched


@pytest.fixture(scope="module")
def timed():
    return *_run(_small_tree(), cluster_factory=Watched), Watched.holds


def test_the_committed_files_state_the_cell():
    manifest = Manifest()
    cell = manifest.cell(CELL)
    mix = manifest.traffic(cell)
    drain = manifest.traffic(manifest.cell("sched-perf-5k.drain"))
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "sched-perf-5k", "backlog", 1)
    assert manifest.config(cell)["wave_pods"] == 10000
    assert mix["kind"] == "backlog" and mix["warm_rounds"] == 1
    for key in ("create_window", "warm_min_chunks", "warm_bursts",
                "chunk_counter", "barrier_seconds", "trace_seconds",
                "readback_sample", "end_to_end", "wave_line_counters"):
        assert mix[key] == drain[key], key
    kind = manifest.kind("backlog")
    assert callable(kind.warm) and callable(kind.window)
    listing = {m["name"]: m for m in manifest.doc["end_to_end"]
               + manifest.doc["per_layer"]}
    for name in ("pods_bound_per_s", "frag_occupied_pct",
                 "create_ack_p50_ms.drain", "prep_ms_per_kpod.drain",
                 "solve_wait_ms_per_chunk.drain",
                 "device_busy_ms_per_kpod.drain", "device_idle_pct.drain",
                 "peak_hbm_mb.drain", "compiles_in_window.drain",
                 "trace_lower_s_in_window.drain",
                 "mask_solve_update_roofline.drain"):
        assert CELL in listing[name]["workloads"], name
    skipped = listing["scan_steps_skipped_pct.backlog"]
    assert skipped["workloads"] == [CELL]
    assert (skipped["moves"], skipped["better"], skipped["layer"]) == (
        "pods_bound_per_s", "higher", "device solve")
    assert manifest.metric_file("scan_steps_skipped_pct.backlog")["args"] \
        == manifest.metric_file("scan_steps_skipped_pct.trickle")["args"]


def test_the_cell_runs_correct_on_the_batch_path(timed):
    rc, result, err, _ = timed
    assert rc == 0 and result["correct"] is True, err[-3000:]
    assert result["failed"] == 0
    assert result["attempted"] >= WAVE and result["attempted"] % WAVE == 0
    assert all(n == {"value": 0, "limit": 0}
               for n in result["compared"].values())
    assert set(result["metrics"]) == {
        "pods_bound_per_s", "frag_occupied_pct", "setup_s"}
    rounds = _window(err)["waves"]
    assert all(r["chunks"] >= 1 and not r["fast_path"] for r in rounds)


def test_nothing_binds_while_held_and_a_full_backlog_is_released(timed):
    *_, holds = timed
    assert len(holds) >= 3            # warm-up rounds and the window's
    for held in holds:
        assert held["bound_at_release"] == held["bound_at_hold"]
        assert held["queued"] == WAVE and held["in_flight"] == 0


def test_the_rate_is_bound_pods_over_the_summed_drain_stretches(timed):
    _, result, err, _ = timed
    window = _window(err)
    rounds = window["waves"]
    assert sum(r["pods"] for r in rounds) == result["attempted"]
    stretch = sum(r["seconds"] for r in rounds)
    assert result["metrics"]["pods_bound_per_s"]["value"] == pytest.approx(
        result["attempted"] / stretch, rel=1e-3)     # the line is rounded
    # the creates are outside the stretches: the window is longer
    assert all(r["create_seconds"] > 0 for r in rounds)
    if len(rounds) > 1:
        assert window["seconds"] > stretch


def test_the_traced_run_reads_the_scan_steps(timed):
    rc, result, err = _run(_small_tree(), trace=True)
    assert rc == 0 and result["correct"] is True, err[-3000:]
    metrics = result["metrics"]
    # a chunk of 600 of 1,024: the serial scan skips 424 steps of 1,024
    assert 0 < metrics["scan_steps_skipped_pct.backlog"]["value"] < 100
    assert metrics["create_ack_p50_ms.drain"]["value"] > 0
    assert "device_busy_ms_per_kpod.drain" not in metrics


@pytest.mark.parametrize("sound", [True, False])
def test_without_the_seam_the_kind_raises_at_once(sound):
    """The control's scheduler (and a program from before the seam)
    cannot stand by: the run ends in an error that names what is
    missing, within seconds, with nothing run unheld."""
    tree = _small_tree()
    model = tree.deployment(tree.config(tree.cell(CELL)))
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match=r"hold\(\), release\(\)") as e:
        _run(tree, cluster_factory=control_cluster(model, sound))
    assert "ReferenceScheduler" in str(e.value)
    assert "Nothing was run unheld" in str(e.value)
    assert time.monotonic() - t0 < 30
