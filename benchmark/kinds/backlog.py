"""backlog — the scheduler starts on pods that are already pending: what
a restarted scheduler, or a standby that wins the lease, finds, and what
upstream scheduler_perf measures when it times scheduling apart from
creation.

A round: hold the scheduler (the program's `Scheduler.hold()`); create
the configuration's `wave_pods` pods in `create_window`-wide windows and
wait until every create is acknowledged AND the scheduler's own informer
has queued them all; release; wait until the client's watch has shown
every one bound. Warm-up is `warm_rounds` such rounds, more until the
program has solved `warm_min_chunks` chunks, then `warm_bursts` (small
bursts created with the scheduler running, for the programs a ragged
pop can take). The window is rounds back to back until `seconds` have
passed since the first release; a round begun is finished. `on_start`
is awaited immediately before the first release, so the first round's
creates are set-up and a traced stretch opens on a draining backlog.

`bound_per_s` is the pods the client saw bound over the summed drain
stretches (release to the last binding seen, one per round): one sum,
one division. No create is inside a stretch.

The hold is the program's. Where its scheduler has no such seam the
kind raises at once, naming what is missing; it never runs unheld.
"""

import asyncio
import time

from benchmark.lib import counters
from benchmark.lib.traffic import Window


def _scheduler(gen):
    """The cluster's scheduler, if it can stand by and say what it has
    queued."""
    sched = gen.cluster.sched
    missing = [what for what, there in (
        ("hold()", callable(getattr(sched, "hold", None))),
        ("release()", callable(getattr(sched, "release", None))),
        ("queue.stats()", callable(getattr(
            getattr(sched, "queue", None), "stats", None)))) if not there]
    if missing:
        raise RuntimeError(
            f"traffic kind 'backlog' needs a scheduler that can stand by "
            f"while pods become pending; {type(sched).__name__} has no "
            f"{', '.join(missing)}. Nothing was run unheld.")
    return sched


async def _round(gen, phase: str, names: list[str], ack=None,
                 before_release=None) -> dict:
    """One round; returns its keys, its two stretches and what never
    bound."""
    sched = _scheduler(gen)
    await sched.hold()
    try:
        t0 = time.monotonic()
        sent = await gen.create_wave(phase, names, ack)
        deadline = time.monotonic() + gen.barrier_s
        while sched.queue.stats()["active"] < len(sent):
            if time.monotonic() >= deadline:
                raise RuntimeError(
                    f"backlog: {sched.queue.stats()['active']} of "
                    f"{len(sent)} pods queued after {gen.barrier_s} s")
            await asyncio.sleep(0.005)
        t1 = time.monotonic()
        if before_release is not None:
            await before_release()
    finally:
        released = time.monotonic()
        await sched.release()
    unbound = await gen.settle(sent)
    return {"sent": sent, "unbound": unbound, "held": t0, "queued": t1,
            "released": released, "waited": time.monotonic(),
            "last_bound": gen.last_bound(sent, released)}


async def warm(gen) -> None:
    size = int(gen.config["wave_pods"])
    need = int(gen.mix.get("warm_min_chunks", 0))
    counter = gen.mix.get("chunk_counter", "")
    registry = gen.cluster.metrics.registry
    r = 0

    def more_chunks_needed() -> bool:
        done = counters.total(counters.snapshot(registry), counter)
        return done is not None and done < need and r < 8
    while r < int(gen.mix.get("warm_rounds", 1)) or (
            need and more_chunks_needed()):
        got = await _round(gen, "warm", [f"warm{r}-{i}" for i in range(size)])
        if got["unbound"]:
            raise RuntimeError(
                f"warm-up round {r}: {got['unbound']} pods unbound")
        r += 1
    for size in gen.mix.get("warm_bursts", []):
        names = [f"burst{size}-{i}" for i in range(int(size))]
        left = await gen.settle(await gen.create_wave("burst", names))
        if left:
            raise RuntimeError(f"warm-up burst {size}: {left} unbound")


async def window(gen, seconds: float, on_start) -> Window:
    win = Window()
    size = int(gen.config["wave_pods"])
    tag = f"s{gen.seed:x}"
    ack = win.series.setdefault("create_ack_ms", [])
    line_names = gen.mix.get("wave_line_counters", {})
    registry = gen.cluster.metrics.registry
    drained = 0.0
    stop_at = None
    k = 0
    while stop_at is None or time.monotonic() < stop_at:
        snap0 = counters.snapshot(registry) if line_names else {}
        got = await _round(
            gen, "measured", [f"{tag}-w{k}-{i}" for i in range(size)], ack,
            on_start if k == 0 else None)
        if k == 0:
            win.start = got["released"]
            stop_at = win.start + seconds
            # packing is judged on the cluster as the first round left it
            win.packing_upto = len(gen.all_created)
        win.spans.append(("bench.create", got["held"], got["queued"]))
        win.spans.append(("bench.wait_bound", got["released"], got["waited"]))
        win.created += got["sent"]
        win.unbound += got["unbound"]
        win.end = max(win.end, got["last_bound"])
        drained += got["last_bound"] - got["released"]
        line = {"pods": len(got["sent"]) - got["unbound"],
                "seconds": got["last_bound"] - got["released"],
                "create_seconds": got["queued"] - got["held"]}
        if line_names:
            snap1 = counters.snapshot(registry)
            for label, name in line_names.items():
                line[label] = counters.delta(snap0, snap1, name)
        if gen.compile_log is not None:
            inside = gen.compile_log.window(got["released"], got["last_bound"])
            line["compiles"] = inside["compiles"]
            line["trace_lower_s"] = inside["trace_lower_seconds"]
        if gen.gc_log is not None:
            line["gc"] = gen.gc_log.window(got["released"], got["last_bound"])
        win.waves.append(line)
        k += 1
        if got["unbound"]:
            break
    bound = len(win.created) - win.unbound
    win.quantities["bound_per_s"] = bound / drained if drained > 0 else 0.0
    return win
