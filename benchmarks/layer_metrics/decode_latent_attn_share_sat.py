"""``decode.latent_attn_share.sat``: the device time the kernel
``latent_decode_attention`` took a decode step, over the decode program's
mean device time in the trace: how much of a step the latent cache's
attention is."""
from .decode_latent_attn_hbm_roofline_sat import kernel_s_a_step


def read(run, trace):
    a_step = kernel_s_a_step(run, trace)
    mean_s = trace.program_mean_s(run.cfg["programs"]["decode"])
    if a_step is None or not mean_s:
        return None
    return 100.0 * a_step / mean_s
