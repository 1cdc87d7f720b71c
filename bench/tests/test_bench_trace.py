"""Trace reduction, metric arithmetic and FLOP/byte counts of the
benchmark, on hand-built traces (no chip, no TPU library)."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import flops, harness, peaks  # noqa: E402
from bench import trace as tl  # noqa: E402
from bench.models import decoder  # noqa: E402

MS = 1_000_000


def test_busy_is_the_union_of_overlapping_ops():
    evs = [("a", 0, 10 * MS), ("b", 5 * MS, 10 * MS), ("c", 30 * MS, 5 * MS)]
    assert tl.busy_ns(evs, 0, 40 * MS) == 20 * MS
    # clipped to the window
    assert tl.busy_ns(evs, 8 * MS, 32 * MS) == 9 * MS


def test_gaps_cover_the_window_outside_ops():
    evs = [("a", 2 * MS, 3 * MS), ("b", 10 * MS, 5 * MS)]
    assert tl.gaps(evs, 0, 20 * MS) == [(0, 2 * MS), (5 * MS, 10 * MS),
                                        (15 * MS, 20 * MS)]
    assert tl.gaps([], 0, MS) == [(0, MS)]


def test_idle_gaps_are_named_by_the_innermost_open_span():
    evs = [("op", 0, 2 * MS), ("op", 10 * MS, 2 * MS)]
    spans = [("bench.window", 0, 21 * MS), ("bench.fetch", 3 * MS, 6 * MS)]
    out = tl.idle_breakdown(evs, spans, 0, 21 * MS)
    assert out[0] == ["bench.window", pytest.approx(9e-3)]
    assert out[1] == ["bench.fetch", pytest.approx(8e-3)]
    assert tl.span_at(spans, 25 * MS) == "host.outside_spans"


def test_top_ops_count_self_time_and_short_names():
    evs = [("%fusion.1 = f32[2] fusion(...)", 0, 3), ("natural_reduce", 3, 5),
           ("%fusion.1 = f32[2] fusion(...)", 8, 4), ("qsgd_pack", 12, 1)]
    assert tl.top_ops(evs, 2) == [["fusion.1", pytest.approx(7e-9)],
                                  ["natural_reduce", pytest.approx(5e-9)]]
    nested = [("%while.3 = (...) while(...)", 0, 100),
              ("%conditional.1 = (...)", 10, 60), ("%dot.2 = f32", 20, 30),
              ("%copy.4 = f32", 80, 10)]
    assert tl.self_times(nested) == {"while.3": 30, "conditional.1": 30,
                                     "dot.2": 30, "copy.4": 10}
    assert [e[0] for e in tl.matching(evs, ["natural", "qsgd"])] == \
        ["natural_reduce", "qsgd_pack"]


def test_window_span_is_required():
    assert tl.window([("bench.window", 5, 10)]) == (5, 15)
    with pytest.raises(ValueError):
        tl.window([("bench.fetch", 5, 10)])


def test_unknown_device_kind_has_no_peaks():
    assert peaks.peaks_for("TPU v5 lite")["flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def test_train_flops_per_token_dense_and_moe():
    dense = {"d_model": 4, "heads": 2, "kv_heads": 1, "head_dim": 2,
             "d_ff": 8, "layers": 3, "vocab": 10}
    # attn 4*(2+2)*2 + 2*2*4 = 48, ffn 3*4*8 = 96, unembed 40
    assert decoder.matmul_params_per_token(dense) == 3 * (48 + 96) + 40
    assert decoder.train_flops_per_token(dense, 5) == \
        6.0 * (3 * 144 + 40) + 12 * 5 * 2 * 2 * 3
    moe = dict(dense, experts=4, experts_per_token=2, expert_width=3)
    # ffn: router 4*4 + 2 * 3 * 4 * 3
    assert decoder.matmul_params_per_token(moe) == 3 * (48 + 16 + 72) + 40


def test_round_kernel_bytes():
    cost = flops.round_kernel_cost("natural", 1000, 2, 128)
    d = 1024
    assert cost["encode"][0] == 2 * 8 * d
    assert cost["reduce"][0] == 2 * d * 1.125 + 4 * d
    assert cost["downlink"][0] == 8 * d
    q = flops.round_kernel_cost("qsgd", 5000, 3, 2048)
    assert q["reduce"][0] == 3 * (6144 + 4 * 3) + 4 * 6144


def _rec(**kw):
    rec = {"chips": 1, "window_s": 2.0, "model_flops": 197e12 * 0.5,
           "peaks": peaks.peaks_for("TPU v5 lite"), "comm_rounds": 4,
           "flat_size": 1000, "params": {"clients": 2,
                                         "uplink": {"name": "natural"}},
           "trace": {"window_s": 2.0, "busy_s": 1.5, "events": []}}
    rec.update(kw)
    return rec


def test_device_idle_and_mfu_readers():
    assert harness.reader("device_idle.train")(_rec()) == pytest.approx(25.0)
    assert harness.reader("mfu.train")(_rec()) == pytest.approx(25.0)
    assert harness.reader("device_idle.train")(_rec(trace=None)) is None


def test_compression_readers_read_kernel_events_only():
    read_ms = harness.reader("compress_ms_per_round.train")
    read_roof = harness.reader("compress_roofline.train")
    from importlib import util
    path = os.path.join(harness.BENCH_DIR, "metrics",
                        "compress_roofline.train.py")
    spec = util.spec_from_file_location("roof", path)
    mod = util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    k = mod.KERNELS["natural"]
    evs = [(k[0], 0, 2 * MS), (k[-1], 2 * MS, MS), (k[0], 3 * MS, MS),
           ("fusion.7", 4 * MS, 50 * MS)]
    rec = _rec(trace={"window_s": 2.0, "busy_s": 1.0, "events": evs})
    assert read_ms(rec) == pytest.approx(4.0 / 4)
    cost = flops.round_kernel_cost("natural", 1000, 2, 128)
    least = sum(max(b / 819e9, o / 197e12) for b, o in cost.values()) * 4
    assert read_roof(rec) == pytest.approx(100 * least / 4e-3)
    # no kernel events: nothing to read, never a zero share
    assert read_roof(_rec()) is None and read_ms(_rec()) is None
