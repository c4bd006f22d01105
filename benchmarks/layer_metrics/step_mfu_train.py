"""``step.mfu.train``: model operations of the traced window's samples over
the chip's bf16 peak. A training sample costs three forward passes' worth, at
two operations a multiply-add (24.54 GFLOP for ResNet-50 at 224x224)."""


def read(run, trace):
    t0, t1 = run.window
    c = run.counters
    if not c.get("samples") or "flops_bf16" not in run.peaks:
        return None
    flops = 3 * 2 * c["macs_per_sample"] * c["samples"]
    chips = int(run.cell["chips"])
    return 100.0 * flops / (t1 - t0) / (run.peaks["flops_bf16"] * chips)
