"""``decode.latent_attn_hbm_roofline.sat``: the least time the HBM could take
to read the latent rows a decode step's attention has to read (one published
row of ``kv_lora_rank + qk_rope_head_dim`` values a live position and layer,
once though it serves as key and as value, whatever padding the layout adds:
``reference.decode_attention_bytes``), over the device time the kernel
``latent_decode_attention`` took a decode step in the trace. Bound by bytes:
60 operations a byte at 32 heads.

The window's decode steps are counted from the tokens stamped inside it: all
but each request's first come from a decode step, and in a saturated cell a
step fills every slot, so ``(tokens_in_window - requests) / slots`` steps
(``requests`` sent in the window stand for the prefills finished in it: a
closed loop sends one as one ends). Not ``sched_steps``: the driver reads the
scheduler's counters after ``stop_trace``, while the requests drain, and in a
traced run of this configuration the count came out double (PERF.md section
7). A step with an empty slot would be counted as part of one, and the share
read high by the empty slots' share (0.2% untraced)."""

KERNEL = "latent_decode_attention"


def kernel_s_a_step(run, trace):
    """The kernel's device time over the decode program's runs in the trace,
    or ``None`` where the trace has neither."""
    kernel_s = trace.op_s.get(KERNEL)
    steps = len(trace.programs.get(run.cfg["programs"]["decode"], ()))
    return kernel_s / steps if kernel_s and steps else None


def read(run, trace):
    c = run.counters
    count = getattr(run.reference, "decode_attention_bytes", None)
    a_step = kernel_s_a_step(run, trace)
    decoded = c.get("tokens_in_window", 0) - c.get("requests", 0)
    if count is None or a_step is None or decoded <= 0:
        return None
    need = count(run.cfg, c["live_positions"] * c["slots"] / decoded)
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / a_step
