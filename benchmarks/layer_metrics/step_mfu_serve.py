"""``step.mfu.serve``: two operations a parameter for every token the window
produced, over the window, over the chip's bf16 peak. Prompt tokens are left
out on purpose: the metric moves ``output_tokens_per_s``."""


def read(run, trace):
    t0, t1 = run.window
    c = run.counters
    if not c.get("tokens_in_window") or "flops_bf16" not in run.peaks:
        return None
    rate = c["ops_per_token"] * c["tokens_in_window"] / (t1 - t0)
    return 100.0 * rate / (run.peaks["flops_bf16"] * int(run.cell["chips"]))
