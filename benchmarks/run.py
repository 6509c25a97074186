"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines:
  table1.*    — paper Table I analogue (blocked matmul config sweep)
  table2.*    — paper Table II analogue (SpMV on the four matrices)
  bandwidth.* — paper §V-B bandwidth-extrapolation figure
  roofline.*  — §Roofline rows from the dry-run artifacts (if present)

and writes ``BENCH_kernels.json`` (``--out`` to relocate): the
machine-readable kernel-perf record tracked across PRs — autotuned tile per
Table-1 shape, model GFLOP/s, tuner-vs-fixed speedup, measured wall-clock
where feasible, and the SpMV tuner plans with the balance metric.
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import tempfile


BENCH_SCHEMA = 3

# --smoke shrinks the wall-clocked shapes so the whole run (plus the
# schema check in tools/check_bench.py) fits a CI smoke job; every report
# key and derived row is still produced.
SMOKE_ATTN_MEASURED = dict(bh=2, seq=128, dh=32, reps=2, trials=2)
SMOKE_CAUSAL_SKIP = dict(bh=1, seq=256, dh=32, block_q=64, block_k=64,
                         reps=2, trials=2)
SMOKE_DECODE = dict(b=1, hq=4, hkv=2, dh=32, cache_len=256, reps=2, trials=2)
SMOKE_RAGGED = dict(b=2, hq=4, hkv=2, dh=32, cache_len=128, block_k=32,
                    reps=2, trials=2)
SMOKE_INT8 = dict(b=1, hq=4, hkv=2, dh=32, cache_len=256, reps=2, trials=2)


def kernel_report(tuned_recs=None, attn_recs=None, attn_measured=None,
                  attn_skip=None, attn_decode=None,
                  attn_ragged=None, attn_int8=None) -> dict:
    import jax

    from benchmarks import attention_prefill, table1_matmul, table2_spmv

    return {
        "schema": BENCH_SCHEMA,
        "backend": jax.default_backend(),
        "host": platform.machine(),
        "matmul_tuned_vs_fixed": (tuned_recs if tuned_recs is not None
                                  else table1_matmul.tuned_vs_fixed()),
        "matmul_measured": table1_matmul.tuned_vs_fixed_measured(),
        "spmv_tuned": table2_spmv.tuned_records(),
        "attention_tuned_vs_fixed": (
            attn_recs if attn_recs is not None
            else attention_prefill.tuned_vs_fixed()),
        "attention_measured": (
            attn_measured if attn_measured is not None
            else attention_prefill.tuned_vs_fixed_measured()),
        "attention_causal_skip": (
            attn_skip if attn_skip is not None
            else attention_prefill.causal_skip_measured()),
        "attention_decode": (
            attn_decode if attn_decode is not None
            else attention_prefill.decode_step_measured()),
        "decode_ragged": (
            attn_ragged if attn_ragged is not None
            else attention_prefill.decode_ragged_measured()),
        "decode_int8": (
            attn_int8 if attn_int8 is not None
            else attention_prefill.decode_int8_measured()),
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_kernels.json",
                    help="path for the machine-readable kernel report")
    ap.add_argument("--skip-json", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="small wall-clocked shapes for the CI smoke job "
                         "(full schema, reduced measurement cost)")
    args = ap.parse_args(argv)
    from repro.launch import compile_cache
    compile_cache.enable()

    # The report must reflect the code under benchmark, not whatever an
    # earlier run left in the user-global autotune cache — tune fresh in a
    # throwaway cache unless the caller explicitly pinned one.
    if "REPRO_AUTOTUNE_CACHE" not in os.environ:
        os.environ["REPRO_AUTOTUNE_CACHE"] = os.path.join(
            tempfile.mkdtemp(prefix="repro-bench-"), "autotune.json")

    from benchmarks import (attention_prefill, bandwidth_extrapolation,
                            roofline_report, table1_matmul, table2_spmv)

    # Tune/measure once; the CSV pass and the JSON report share the records.
    tuned_recs = table1_matmul.tuned_vs_fixed()
    attn_recs = attention_prefill.tuned_vs_fixed()
    attn_measured = attention_prefill.tuned_vs_fixed_measured(
        **(SMOKE_ATTN_MEASURED if args.smoke else {}))
    attn_skip = attention_prefill.causal_skip_measured(
        **(SMOKE_CAUSAL_SKIP if args.smoke else {}))
    attn_decode = attention_prefill.decode_step_measured(
        **(SMOKE_DECODE if args.smoke else {}))
    attn_ragged = attention_prefill.decode_ragged_measured(
        **(SMOKE_RAGGED if args.smoke else {}))
    attn_int8 = attention_prefill.decode_int8_measured(
        **(SMOKE_INT8 if args.smoke else {}))
    lines: list[str] = []
    lines += table1_matmul.main(tuned_recs)
    lines += table2_spmv.main()
    lines += attention_prefill.main(attn_recs, attn_measured, attn_skip,
                                    attn_decode, attn_ragged, attn_int8)
    lines += bandwidth_extrapolation.main()
    try:
        lines += roofline_report.main()
    except Exception as e:  # dry-run artifacts may not exist yet
        lines.append(f"roofline.unavailable,0.0,{e!r}")
    print("name,us_per_call,derived")
    for ln in lines:
        print(ln)

    if not args.skip_json:
        report = kernel_report(tuned_recs, attn_recs, attn_measured,
                               attn_skip, attn_decode, attn_ragged,
                               attn_int8)
        # Atomic temp+fsync+rename: a run killed mid-save leaves the
        # previous committed report, never a torn BENCH_kernels.json.
        from repro.core.ioutil import atomic_write_json
        atomic_write_json(args.out, report)
        print(f"# wrote {args.out}")


if __name__ == "__main__":
    main()
