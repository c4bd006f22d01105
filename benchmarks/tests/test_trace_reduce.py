"""The trace reduction on the chip trace the repo recorded
(``docs/traces/xplane``: eight ResNet-50 steps of 13.68 ms)."""
import os

from conftest import ROOT


def test_union_and_gaps():
    from benchmarks.trace_reduce import union_length
    total, gaps = union_length([(0, 10), (5, 12), (20, 30), (30, 31)])
    assert total == 23 and gaps == [(12, 20)]


def test_names():
    from benchmarks.trace_reduce import op_kind, program_name
    assert op_kind("%convolution_add_fusion.12 = bf16[1]{0} fusion(...)") \
        == "convolution_add_fusion"
    assert op_kind("%copy-start.185 = (f32[64]) copy-start(...)") == "copy-start"
    assert program_name("jit_train_step(1017)") == "jit_train_step"


def test_recorded_trace():
    from benchmarks import trace_reduce
    r = trace_reduce.reduce_dir(os.path.join(ROOT, "docs", "traces", "xplane"))
    assert r.chips == 1
    assert 0 < r.busy_s <= r.window_s
    assert 0 <= r.idle_share() < 0.05          # the steps run back to back
    step = r.program_mean_s("jit_train_step")
    assert abs(step - 13.68e-3) < 0.05e-3
    gaps = r.program_gaps["jit_train_step"]
    assert sum(gaps) / len(gaps) < 1e-3
    b = r.breakdown()
    assert len(b["device_ops"]) <= 10 and b["device_ops"][0][0] == "fusion"
    assert all(s >= 0 for _n, s in b["idle_gaps"])


def test_idle_is_split_by_the_span_that_covered_it():
    from benchmarks.trace_reduce import idle_by_span
    got = idle_by_span([(0, 100), (200, 300)],
                       [("prefill", 50, 80), ("step", 90, 250)])
    assert got == {"prefill": 30, "step": 10 + 50, "no_span": 60 + 50}
