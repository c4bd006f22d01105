"""``prefill.latent_attn_mfu.sat``: the useful operations of the causal
attention of the prefills the trace holds (``reference.prefill_attention_ops``:
from the prompts' TRUE lengths, so a bucket's padding and a tile's masked half
are no work, and the count is the same whatever kernel computes it), over the
chip's bf16 peak, over the device time of the kernel that attends a prefill's
chunk (``latent_prefill_attention`` in the trace,
``mxtpu/ops/pallas_attention.py``). Bound by operations: a tile of 512 x 1024
pairs does 1,024 operations a byte it fetches.

Which prefills the trace holds: the device plane holds the prefill program's
runs that lie wholly inside the window (``trace.programs``), in order, from
the window's start until its buffer is full; the scheduler's own spans
``serve.gen.prefill`` (``mxtpu.obs``, argument ``plen``) that start inside
the window are the same prefills in the same order, and in a traced run each
holds its device run (the harness's wrapper waits inside it). So the first
``n`` spans give the ``n`` runs' lengths. A prefill cut by either end of the
plane leaves part of its kernel time in the sum and no run in the count: the
share reads low by up to two prefills' part in ``n``. A trace with no prefill
run, a program without the kernel (any commit before PR 36) or without the
spans reads nothing: ``None``, never 0."""

KERNEL = "latent_prefill_attention"


def prompt_lengths(run):
    """``plen`` of the scheduler's prefill spans that start in the window,
    in order; [] where the program records none."""
    try:
        from mxtpu import profiler
    except ImportError:
        return []
    offset_us = getattr(profiler, "EPOCH_OFFSET_US", None)
    if offset_us is None:
        return []
    t0, t1 = run.window
    found = []
    for e in profiler.snapshot_events():
        if e.get("name") != "serve.gen.prefill" or e.get("ph") != "X":
            continue
        start = (e["ts"] - offset_us) * 1e-6
        plen = e.get("args", {}).get("plen")
        if plen is not None and t0 <= start < t1:
            found.append((start, int(plen)))
    return [plen for _start, plen in sorted(found)]


def read(run, trace):
    count = getattr(run.reference, "prefill_attention_ops", None)
    kernel_s = trace.op_s.get(KERNEL)
    runs = len(trace.programs.get(run.cfg["programs"]["prefill"], ()))
    lengths = prompt_lengths(run)
    if (count is None or not kernel_s or not runs or len(lengths) < runs
            or "flops_bf16" not in run.peaks):
        return None
    return 100.0 * count(run.cfg, lengths[:runs]) / run.peaks["flops_bf16"] \
        / kernel_s
