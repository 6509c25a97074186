"""Run one cell of the on-chip serving benchmark.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, with nothing set in the environment: the
script puts the checkout and its ``src/`` on its own path.  It needs the
chips the cell asks for and exits non-zero, printing no result, where JAX
finds none.  The last line of standard output is the result object;
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window.
"""

import time

T0 = time.perf_counter()          # set-up is timed from process start

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _finite(obj):
    """JSON has no infinity: a check that read nothing reads null."""
    if isinstance(obj, float) and not math.isfinite(obj):
        return None
    if isinstance(obj, dict):
        return {k: _finite(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_finite(v) for v in obj]
    return obj


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"run.py: the system under test (src/repro) is not in "
              f"{ROOT}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), t_start=T0)
    except harness.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 3
    print(json.dumps(_finite(result)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
