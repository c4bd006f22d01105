"""``decode.attn_hbm_roofline.sat``: the least time the HBM could take to read
the key and value rows a decode step's attention has to read (all live rows
on full layers, at most the window on window layers;
``reference.decode_attention_bytes``), over the device time the kernel
``decode_attention`` took a decode step in the trace. Bound by bytes."""


def read(run, trace):
    c = run.counters
    count = getattr(run.reference, "decode_attention_bytes", None)
    kernel_s = trace.op_s.get("decode_attention")
    steps = len(trace.programs.get(run.cfg["programs"]["decode"], ()))
    if count is None or not kernel_s or not steps or not c.get("sched_steps"):
        return None
    need = count(run.cfg, c["live_positions"] / c["sched_steps"])
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / (kernel_s / steps)
