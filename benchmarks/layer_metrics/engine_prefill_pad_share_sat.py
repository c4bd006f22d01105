"""``engine.prefill_pad_share.sat``: of the rows the prefill programs ran,
the share that was padding: 1 - ``serve.gen.prefill_rows`` (the prompts' true
lengths) over ``serve.gen.prefill_rows_padded`` (their buckets), the
scheduler's counters, kept for the process so that they outlive the
scheduler the harness stops before it reads. Both count since the process
started (the warm-up's one prompt a bucket, the ramp, the window and what the
queue still held at its close): the driver keeps no reading of them at the
window's ends, and every block of the pool holds the same lengths, so the
window's share is the pool's. A program without the counters (any commit before PR 36) reads
nothing."""
from .moe_held_share_sat import registry


def read(run, trace):
    rows = sum(registry("serve.gen.prefill_rows"))
    padded = sum(registry("serve.gen.prefill_rows_padded"))
    if not rows or not padded:
        return None
    return 100.0 * (1.0 - rows / padded)
