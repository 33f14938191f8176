"""A traffic kind as a later PR would bring it, over the Generator's
services: warm-up is a burst of `warm_pods`, the window is ONE wave of
the configuration's `wave_pods`, whatever `seconds` says."""

import time

from benchmark.lib.traffic import Window


async def warm(gen) -> None:
    names = [f"warm-{i}" for i in range(int(gen.mix["warm_pods"]))]
    left = await gen.settle(await gen.create_wave("warm", names))
    if left:
        raise RuntimeError(f"warm-up: {left} pods unbound")


async def window(gen, seconds: float, on_start) -> Window:
    win = Window()
    names = [f"s{gen.seed:x}-{i}" for i in range(int(gen.config["wave_pods"]))]
    if on_start is not None:
        await on_start()
    win.start = time.monotonic()
    win.created = await gen.create_wave(
        "measured", names, win.series.setdefault("create_ack_ms", []))
    t1 = time.monotonic()
    win.spans.append(("bench.create", win.start, t1))
    win.unbound = await gen.settle(win.created)
    win.end = gen.last_bound(win.created, t1)
    win.packing_upto = len(gen.all_created)
    win.quantities["bound_per_s"] = \
        (len(win.created) - win.unbound) / (win.end - win.start)
    return win
