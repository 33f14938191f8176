"""The control of a cell, run as the cell is: the plain reference in the
program's place with one guarantee broken (lib/control.py) — which one
is the deployment's to say (lib/reference.py: `placer`; the default
places 1,024 pods, the program's chunk width, by one look at the
cluster). The last line's `correct` has to read false.

    python3 benchmark/control.py --workload <name> --seed <n> --seconds <s> [--sound]

`--sound` runs the reference unbroken (one look per pod): `correct` has
to read true, which shows that it is the fault, not the reference, that
fails. The benchmark's own runs never run this. It drives no device, so
any host will do: the result names the platform it found.
"""

import time

_T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--sound", action="store_true")
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    from benchmark.lib.control import control_cluster
    from benchmark.lib.harness import Refused, run_cell
    from benchmark.lib.manifest import Manifest
    manifest = Manifest()
    model = manifest.deployment(manifest.config(manifest.cell(args.workload)))
    try:
        return run_cell(
            args.workload, args.seed, args.seconds, False,
            manifest=manifest, t_process=_T_PROCESS, require_chip=False,
            cluster_factory=control_cluster(model, args.sound))
    except Refused as e:
        print(f"benchmark: refused: {e}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    raise SystemExit(main())
