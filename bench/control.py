"""The lower-precision controls of a cell's check, on the chip.

    python bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

For each seed, one run of the cell at its own load and sizes (a window of
``--seconds``, then the drain), whose check also reads, at the same
prompts and served tokens, the widest gap of the tokens that the int8 and
the fp8 (e4m3) control would put first, and judges each against the
cell's limit as it judges the program.  One JSON line per seed: for the
program and each control, its widest gap, the limit and whether it came
out correct.  The cell's limit lies between the largest program reading
and the smallest control reading (PERF.md); a sound control comes out not
correct.  The benchmark's own runs do not run the controls.
"""

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", nargs="*", default=["int8", "fp8"])
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    for seed in args.seeds:
        t0 = time.perf_counter()
        res = harness.run_cell(args.workload, seed, args.seconds, False,
                               controls=tuple(args.controls))
        checks = res["checks"]
        print(json.dumps({"control": {
            "workload": args.workload, "seed": seed,
            "failed": res["failed"], "seconds": time.perf_counter() - t0,
            "program": checks["logit_gap"],
            **{q: checks[f"{q}_control_gap"] for q in args.controls}}}),
            flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
