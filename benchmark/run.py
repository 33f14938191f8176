"""The benchmark's one command: run one cell once, in one process.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix and per-layer metrics are the
files BENCHMARK.json names (lib/manifest.py); nothing of one cell is in
this file. The last line of standard output is the result object.
"""

import time

_T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fix_hash_seed() -> None:
    """The same work from the same seed: str hashes, and with them the
    order of every set and the collisions of every dict in the control
    plane, change from process to process unless PYTHONHASHSEED is set.
    It has to be set before the interpreter starts, so start again."""
    if os.environ.get("PYTHONHASHSEED") != "0":
        os.environ["PYTHONHASHSEED"] = "0"
        os.execv(sys.executable, [sys.executable] + sys.argv)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if argv is None:
        _fix_hash_seed()

    sys.path.insert(0, ROOT)
    if importlib.util.find_spec("kubernetes_tpu") is None:
        print("benchmark: refused: the program (kubernetes_tpu/) is not in "
              f"{ROOT}; the benchmark alone runs nothing", file=sys.stderr)
        return 3
    from benchmark.lib.harness import Refused, run_cell
    try:
        return run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace), t_process=_T_PROCESS)
    except Refused as e:
        print(f"benchmark: refused: {e}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    raise SystemExit(main())
