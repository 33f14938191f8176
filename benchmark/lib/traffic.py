"""The one general load generator. A traffic mix is a data file
(`benchmark/traffic/<mix>.json`) naming a `kind` and its parameters:

closed_waves — a closed loop. A wave is `wave_pods` creates sent the way
    upstream's createPods sends them (`create_window`-wide concurrent
    windows over the wire); wave k+1 starts when the client has seen
    every pod of wave k bound. Warm-up is `warm_waves` unmeasured waves
    of the same size on the same standing cluster, more until the
    program has solved `warm_min_chunks` chunks, then `warm_bursts`
    (small bursts, for the programs a wave's ragged end can take). The window is waves
    back to back until `seconds` have passed, then a wait for what was
    created; it ends at the last binding the client saw. Nothing else is
    in it: no delete, no rebuild, no sleep.

open_loop — an open loop at a fixed `rate`. Arrival gaps are the
    benchmark's Poisson gaps (lib/arrivals.py) drawn once from the mix's
    `base_seed`; `--seed` only reorders them, so every seed sends the
    same number of pods with the same set of gaps. Each create is sent
    at its due time whether or not earlier pods are bound, and each pod
    is timed from when it was DUE to when the client saw it bound.
    Warm-up sends the same process unmeasured until nothing has
    compiled for `warm_quiet_seconds` (at most `warm_cap_seconds`).

Both report generic quantities; the mix's `end_to_end` table says which
end-to-end metric of BENCHMARK.json is which quantity.

A `kind` that is not defined here is `benchmark/kinds/<kind>.py`, found
by name (lib/manifest.py): a module with `warm(gen)` and
`window(gen, seconds, on_start) -> Window` over this Generator's
services — `create_wave`, `settle`, `last_bound`, `send_open_loop`.

What the nodes and the pods ARE is the deployment's to say
(lib/reference.py): the generator asks it for the cluster to stage and,
for each phase and list of names, for the arguments of each pod.
"""

from __future__ import annotations

import asyncio
import copy
import random
import time

from benchmark.lib import counters
from benchmark.lib.arrivals import poisson_timeline, stable_seed
from benchmark.lib.percentiles import percentiles
from benchmark.lib.reference import pod_key


class Window:
    """What one measured window did, on the client's clock."""

    def __init__(self):
        self.start = 0.0          # first create of the window
        self.end = 0.0            # last binding the client saw
        self.created: list[str] = []      # keys whose create was acked
        #: packing is judged on the first this many of the generator's
        #: `all_created` (None: not judged)
        self.packing_upto: int | None = None
        self.quantities: dict[str, float] = {}
        #: client-clock series, milliseconds, for the per-layer readers
        self.series: dict[str, list[float]] = {}
        #: the benchmark's own spans (name, start, end), time.monotonic
        self.spans: list[tuple[str, float, float]] = []
        self.waves: list[dict] = []
        self.unbound = 0


class Generator:
    def __init__(self, cluster, model, mix: dict, seed: int,
                 compile_log=None, gc_log=None, kind=None):
        from kubernetes_tpu.api.types import make_node, make_pod
        self._make_node, self._make_pod = make_node, make_pod
        self.cluster = cluster
        #: the deployment (lib/reference.py): the nodes and the pods
        self.model = model
        self.config = model.config
        self.mix = mix
        #: the module of kinds/<kind>.py; None for a kind defined here
        self.kind = kind
        self.seed = int(seed)
        self.compile_log = compile_log
        self.gc_log = gc_log
        self.width = int(mix.get("create_window", 512))
        self.barrier_s = float(mix.get("barrier_seconds", 60.0))
        #: every key created so far, set-up included, and beside each
        #: the arguments its pod was made from
        self.all_created: list[str] = []
        self.all_specs: list[dict] = []
        #: lengths of `all_created` at which every pod created so far
        #: had been seen bound (the end of each wave)
        self.settled: list[int] = []

    @staticmethod
    def defines(kind: str) -> bool:
        return hasattr(Generator, f"_window_{kind}")

    # -- staging (set-up) --------------------------------------------------

    async def stage(self) -> None:
        """The deployment's nodes, then its init pods, bound."""
        staged = self.model.nodes()
        for lo in range(0, len(staged), self.width):
            await asyncio.gather(*(
                self.cluster.client.create("nodes", self._make_node(
                    name, **copy.deepcopy(kw)))
                for name, kw in staged[lo:lo + self.width]))
        names = [f"init-{i}" for i in range(int(self.config["init_pods"]))]
        await self.settle(await self.create_wave("init", names))

    # -- what every kind is made of ----------------------------------------

    async def create_wave(self, phase: str, names: list[str],
                          ack_ms: list | None = None,
                          stop_at: float | None = None) -> list[str]:
        """Create pods in `width`-wide concurrent windows; returns the
        keys sent (all, unless `stop_at` passed between windows)."""
        sent: list[str] = []
        for lo in range(0, len(names), self.width):
            if stop_at is not None and time.monotonic() >= stop_at:
                break
            part = names[lo:lo + self.width]
            specs = self.model.pods(phase, part)
            t0 = time.monotonic()
            pods = [self._make_pod(name, **copy.deepcopy(kw))
                    for name, kw in zip(part, specs)]
            await asyncio.gather(*(
                self.cluster.client.create("pods", pod) for pod in pods))
            if ack_ms is not None:
                ack_ms.append(1e3 * (time.monotonic() - t0))
            # a pod's key comes from the object that was created
            sent += [pod_key(pod) for pod in pods]
            self.all_specs += specs
        self.all_created += sent
        return sent

    async def settle(self, keys: list[str]) -> int:
        """Wait for these pods' bindings; returns how many never came."""
        await self.cluster.wait_bound(keys, time.monotonic() + self.barrier_s)
        left = sum(1 for k in keys if k not in self.cluster.bound)
        if not left:
            self.settled.append(len(self.all_created))
        return left

    def last_bound(self, keys: list[str], default: float) -> float:
        at = self.cluster.bound_at
        return max((at[k] for k in keys if k in at), default=default)

    # -- the kinds ---------------------------------------------------------

    async def warm(self) -> None:
        if self.kind is not None:
            await self.kind.warm(self)
        else:
            await getattr(self, f"_warm_{self.mix['kind']}")()

    async def window(self, seconds: float, on_start=None) -> Window:
        if self.kind is not None:
            return await self.kind.window(self, float(seconds), on_start)
        return await getattr(self, f"_window_{self.mix['kind']}")(
            float(seconds), on_start)

    # closed_waves

    async def _warm_closed_waves(self) -> None:
        """`warm_waves` waves, and more until the program has solved
        `warm_min_chunks` chunks (its pipeline depth settles after a
        number of chunks, not of waves)."""
        size = int(self.config["wave_pods"])
        need = int(self.mix.get("warm_min_chunks", 0))
        counter = self.mix.get("chunk_counter", "")
        registry = self.cluster.metrics.registry
        w = 0

        def more_chunks_needed() -> bool:
            done = counters.total(counters.snapshot(registry), counter)
            # no such counter: nothing here solves in chunks (the control)
            return done is not None and done < need and w < 8
        while w < int(self.mix.get("warm_waves", 1)) or (
                need and more_chunks_needed()):
            names = [f"warm{w}-{i}" for i in range(size)]
            left = await self.settle(await self.create_wave("warm", names))
            if left:
                raise RuntimeError(f"warm-up wave {w}: {left} pods unbound")
            w += 1
        # A wave's last pop can catch a handful of pods, or one: those
        # take other programs (small greedy chunk, single-pod fast path)
        # than a full chunk does. Bursts of such sizes mint them here.
        for size in self.mix.get("warm_bursts", []):
            names = [f"burst{size}-{i}" for i in range(int(size))]
            left = await self.settle(await self.create_wave("burst", names))
            if left:
                raise RuntimeError(f"warm-up burst {size}: {left} unbound")

    async def _window_closed_waves(self, seconds: float, on_start) -> Window:
        win = Window()
        size = int(self.config["wave_pods"])
        tag = f"s{self.seed:x}"
        ack: list[float] = []
        win.series["create_ack_ms"] = ack
        line_names = self.mix.get("wave_line_counters", {})
        registry = self.cluster.metrics.registry
        if on_start is not None:
            await on_start()
        win.start = time.monotonic()
        stop_at = win.start + seconds
        k = 0
        while time.monotonic() < stop_at:
            snap0 = counters.snapshot(registry) if line_names else {}
            t0 = time.monotonic()
            names = [f"{tag}-w{k}-{i}" for i in range(size)]
            sent = await self.create_wave("measured", names, ack, stop_at)
            t1 = time.monotonic()
            win.spans.append(("bench.create", t0, t1))
            unbound = await self.settle(sent)
            t2 = self.last_bound(sent, t1)
            win.spans.append(("bench.wait_bound", t1, time.monotonic()))
            win.created += sent
            win.unbound += unbound
            if k == 0:
                # packing is judged on the cluster as the first wave left
                # it: the same pods in every run, whatever the speed
                win.packing_upto = len(self.all_created)
            wave = {"pods": len(sent) - unbound, "seconds": t2 - t0,
                    "create_seconds": t1 - t0}
            if line_names:
                snap1 = counters.snapshot(registry)
                for label, name in line_names.items():
                    wave[label] = counters.delta(snap0, snap1, name)
            if self.compile_log is not None:
                inside = self.compile_log.window(t0, t2)
                wave["compiles"] = inside["compiles"]
                wave["trace_lower_s"] = inside["trace_lower_seconds"]
            if self.gc_log is not None:
                wave["gc"] = self.gc_log.window(t0, t2)
            win.waves.append(wave)
            win.end = max(win.end, t2)
            k += 1
            if unbound:
                break
        bound = len(win.created) - win.unbound
        span = win.end - win.start
        win.quantities["bound_per_s"] = bound / span if span > 0 else 0.0
        return win

    # open_loop

    def _gaps(self, seconds: float, seed: int) -> list[float]:
        """The mix's Poisson gaps for a window of this length, in the
        order `seed` puts them."""
        base = poisson_timeline(float(self.mix["rate"]),
                                int(self.mix.get("base_seed", 0)), seconds)
        gaps = [b - a for a, b in zip([0.0] + base[:-1], base)]
        random.Random(stable_seed("order", seed)).shuffle(gaps)
        return gaps

    async def send_open_loop(self, phase: str, prefix: str,
                             gaps: list[float], t0: float, until=None):
        """Send one create per gap on the absolute clock from t0. Returns
        (keys, due times, lateness ms, ack ms). `until` ends the sending
        early (warm-up)."""
        names = [f"{prefix}-{i}" for i in range(len(gaps))]
        specs = self.model.pods(phase, names)
        keys: list[str] = []
        due: list[float] = []
        late_ms: list[float] = []
        ack_ms: list[float] = []
        tasks: set[asyncio.Task] = set()
        errors: list[BaseException] = []

        async def create(i: int) -> None:
            t = time.monotonic()
            pod = self._make_pod(names[i], **copy.deepcopy(specs[i]))
            try:
                await self.cluster.client.create("pods", pod)
            except Exception as e:  # counted: the pod was not acknowledged
                errors.append(e)
                return
            ack_ms.append(1e3 * (time.monotonic() - t))
            keys[i] = pod_key(pod)

        offset = 0.0
        for i, gap in enumerate(gaps):
            offset += gap
            delay = (t0 + offset) - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            if until is not None and until():
                break
            keys.append("")
            due.append(t0 + offset)
            late_ms.append(1e3 * max(0.0, time.monotonic() - (t0 + offset)))
            task = asyncio.ensure_future(create(i))
            tasks.add(task)
            task.add_done_callback(tasks.discard)
        if tasks:
            await asyncio.gather(*tasks)
        if errors:
            raise RuntimeError(
                f"{len(errors)} creates failed, first: {errors[0]!r}")
        self.all_created += keys
        self.all_specs += specs[:len(keys)]
        return keys, due, late_ms, ack_ms

    async def _warm_open_loop(self) -> None:
        quiet = float(self.mix.get("warm_quiet_seconds", 4.0))
        cap = float(self.mix.get("warm_cap_seconds", 30.0))
        t0 = time.monotonic()
        log = self.compile_log

        def done() -> bool:
            now = time.monotonic()
            if log is None:
                return now - t0 >= quiet
            return now - max(log.last_event(), t0) >= quiet
        gaps = self._gaps(cap, stable_seed("warm", self.seed))
        keys, *_ = await self.send_open_loop(
            "warm", "warm", gaps, t0, until=done)
        left = await self.settle(keys)
        if left:
            raise RuntimeError(f"warm-up arrivals: {left} pods unbound")

    async def _window_open_loop(self, seconds: float, on_start) -> Window:
        win = Window()
        gaps = self._gaps(seconds, self.seed)
        if on_start is not None:
            await on_start()
        win.start = time.monotonic()
        keys, due, late_ms, ack_ms = await self.send_open_loop(
            "measured", f"s{self.seed:x}", gaps, win.start)
        t1 = time.monotonic()
        win.spans.append(("bench.arrival", win.start, t1))
        win.unbound = await self.settle(keys)
        win.spans.append(("bench.wait_bound", t1, time.monotonic()))
        win.created = keys
        win.end = self.last_bound(keys, t1)
        at = self.cluster.bound_at
        # a pod that never bound lies beyond every percentile
        latency = [1e3 * (at[k] - d) if k in at else float("inf")
                   for k, d in zip(win.created, due)]
        win.series.update(latency_ms=latency, gen_late_ms=late_ms,
                          create_ack_ms=ack_ms)
        win.packing_upto = len(self.all_created)
        p = percentiles(latency, (0.5, 0.95))
        win.quantities["latency_p50_ms"] = p[0.5]
        win.quantities["latency_p95_ms"] = p[0.95]
        span = win.end - win.start
        win.quantities["bound_per_s"] = \
            (len(keys) - win.unbound) / span if span > 0 else 0.0
        return win
