"""Find a cell's knee on the chip: the highest offered rate at which the
queue of waiting requests does not grow through the window.

    python bench/knee.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 1.0 2.0 3.0

One process builds the cell's server once and serves one window per rate,
in the order given, draining each before the next.  Each rate prints one
JSON line: the offered rate, the mean queue length in the window's first
and last quarters, the TTFT and inter-token tails, and tokens/s.  The
cell's fixed rate is set to about four fifths of the knee this finds.
"""

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import numpy as np

    from bench import harness, traffic
    cell = harness.load_cell(args.workload)
    harness.prepare(cell)
    rig = harness.build(cell, args.seed)
    for rate in args.rates:
        # No deadline: above the knee requests wait long, and a request
        # that times out while it rides a chunked prefill stops the serve
        # loop (PERF.md, Open questions).
        mix = {**cell["mix"], "deadline_s": 3600.0,
               "arrival": {**cell["mix"]["arrival"], "rate_rps": rate}}
        rig.mix = mix
        reqs = traffic.requests_for(mix, args.seed, args.seconds,
                                    rig.cfg.vocab_size)
        win = harness.measure(rig, reqs, args.seconds)
        lat = harness.latency(win.lc, win.source, win.clock, win.drain_end)
        q = [(t - win.w0, n) for t, n in win.source.queue_trace
             if t < win.w0 + args.seconds]
        quarter = args.seconds / 4
        first = [n for t, n in q if t < quarter]
        last = [n for t, n in q if t >= 3 * quarter]
        e2e = harness.end_to_end(
            ["output_tok_s", "ttft_p50_ms", "ttft_p90_ms", "itl_p50_ms",
             "itl_p95_ms", "itl_p99_ms"],
            lat, win.clock, win.w0, args.seconds, 0.0)
        print(json.dumps({
            "rate_rps": rate, "attempted": lat["attempted"],
            "failed": lat["failed"],
            "queued_first_quarter": float(np.mean(first)) if first else 0.0,
            "queued_last_quarter": float(np.mean(last)) if last else 0.0,
            "drain_s": win.drain_end - win.w0 - args.seconds,
            "compiles_in_window": win.compiles,
            **{k: v["value"] for k, v in e2e.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
