"""The reduction from a profiler trace to the per-layer numbers: busy and
idle as a union of device intervals, device time per host span, kernel
time, and the breakdown; on hand-made intervals and on a small trace
recorded on a TPU v5e."""

import pathlib
import re

import pytest

from bench import trace_reduce as tr

DATA = pathlib.Path(__file__).resolve().parent / "data"


def made():
    ops = [[("fusion.1", 1.0, 2.0), ("fusion.2", 1.5, 2.5),
            ("ragged_flash", 4.0, 5.0), ("fusion.1", 8.0, 8.5),
            ("fusion.1", 11.0, 12.0)]]
    spans = [tr.Span("decode", 0.9, 2.6, {"active": 1, "kv_read": 10}),
             tr.Span("prefill", 3.9, 5.2, {"tokens": 7, "prompts": "7"}),
             tr.Span("pump", 5.5, 7.5, {})]
    return tr.Reduction(ops=ops, spans=spans, window=(0.0, 10.0))


def test_union_merges_overlaps():
    assert tr.union([(3, 4), (1, 2), (1.5, 2.5), (4, 5)]) == [(1, 2.5),
                                                                (3, 5)]
    assert tr.covered([(1, 2.5), (3, 5)], 2, 4) == pytest.approx(1.5)


def test_busy_counts_the_window_only():
    busy, window = made().busy_in_window()
    assert window == 10.0
    assert busy == pytest.approx(1.5 + 1.0 + 0.5)     # not the op at 11 s


def test_device_time_per_span_kind():
    red = made()
    assert red.device_time(("decode",)) == pytest.approx(1.5)
    assert red.device_time(("prefill", "chunk")) == pytest.approx(1.0)
    assert red.device_time(("pump",)) == 0.0


def test_kernel_time_by_name():
    red = made()
    assert red.kernel_time("ragged_flash") == pytest.approx(1.0)
    assert red.kernel_time("fusion") == pytest.approx(1.0 + 1.0 + 0.5)


def test_breakdown_sums_idle_time_by_host_span():
    bd = made().breakdown()
    assert bd["device_ops"][0] == ["fusion.1", pytest.approx(1.5)]
    idle = dict(bd["idle_gaps"])
    # 5.0 .. 8.0 lies two thirds under the pump span; 0 .. 1, 2.5 .. 4 and
    # 8.5 .. 10 under no span for most of their length
    assert idle == {"pump": pytest.approx(3.0),
                    "serve_loop": pytest.approx(1.0 + 1.5 + 1.5)}
    assert [n for n, _ in bd["idle_gaps"]] == ["serve_loop", "pump"]


# A half-second window of qwen3_14b.chat (one request, a 512-token prompt)
# traced on one TPU v5e by `bench/run.py --seconds 0.5 --trace 1`.
RECORDED = DATA / "qwen3_chat_window.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    return tr.reduce_file(RECORDED)


def test_recorded_window_and_spans(recorded):
    lo, hi = recorded.window
    assert hi - lo == pytest.approx(0.5)
    assert len(recorded.ops) == 1 and len(recorded.ops[0]) > 1000
    kinds = {s.kind for s in recorded.spans_in_window(
        ("prefill", "chunk", "decode", "pump"))}
    assert kinds == {"prefill", "decode", "pump"}
    (pre,) = recorded.spans_in_window(("prefill",))
    assert int(pre.stats["tokens"]) == 512
    assert all(int(s.stats["active"]) == 1
               for s in recorded.spans_in_window(("decode",)))


def test_recorded_busy_and_idle_add_up(recorded):
    busy, window = recorded.busy_in_window()
    assert 0.0 < busy < window
    idle = sum(t for _, t in recorded.breakdown()["idle_gaps"])
    assert busy + idle == pytest.approx(window)


def test_recorded_span_attribution(recorded):
    parts = {k: recorded.device_time((k,))
             for k in ("prefill", "decode", "pump")}
    assert parts["decode"] > parts["prefill"] > 0.0
    # spans that start in the window count whole, up to the last one's end
    lo = recorded.window[0]
    hi = max(s.t1 for s in recorded.spans_in_window(tuple(parts)))
    assert sum(parts.values()) <= tr.covered(recorded.merged[0], lo, hi)
    for kind, t in parts.items():
        spans = recorded.spans_in_window((kind,))
        assert t <= sum(s.t1 - s.t0 for s in spans)


# The ragged flash prefill kernel's signature in the trace's op text: a
# tpu_custom_call whose first two operands are the per-row query offsets
# and key counts (two 1-D s32 vectors) that it prefetches.
RAGGED_FLASH = (r"custom-call\(s32\[\d+\]\{[^}]*\} [^,]*, s32\[\d+\]\{[^}]*\} "
                r"[^,]*, bf16\[.*tpu_custom_call")


def test_recorded_kernel_time(recorded):
    kernel = recorded.kernel_time(RAGGED_FLASH)
    # the ragged flash kernel, once per layer of the one prefill, inside
    # the prefill's device time
    assert 0.0 < kernel < recorded.device_time(("prefill",))
    names = [tr.op_name(n) for n, _, _ in recorded.ops[0]
             if re.search(RAGGED_FLASH, n)]
    assert len(names) == 2
    assert all(n.startswith("mha_attention") for n in names)
