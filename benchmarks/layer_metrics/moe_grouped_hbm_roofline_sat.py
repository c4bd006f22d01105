"""``moe.grouped_hbm_roofline.sat``: the least time the HBM could take to read
the routed experts that the window's runs of the expert layers HIT (a held
expert with at least one of a run's assignments; one no token chose is not
read), three matrices each, over the device time of the grouped products
(the Pallas kernel ``gmm`` in the trace,
``mxtpu/parallel/moe.py::grouped_matmul``). Both sides are of ALL runs,
decode steps and prefill chunks alike, so no time is shared out between
programs: the runs are counted in the trace (the decode and the prefill
programs'), and what a run hits is the registry gauge
``ops.moe_ffn.experts_hit_run``, a layer's mean over the runs between the
scheduler's two ``stats()`` readings at the window's ends, summed over the
layers. Bound by bytes."""
from .moe_held_share_sat import registry


def read(run, trace):
    count = getattr(run.reference, "routed_expert_bytes", None)
    grouped_s = trace.op_s.get("gmm")
    hit = registry("ops.moe_ffn.experts_hit_run")
    runs = sum(len(trace.programs.get(run.cfg["programs"][p], ()))
               for p in ("decode", "prefill"))
    if count is None or not grouped_s or not runs or not hit:
        return None
    need = runs * count(run.cfg, experts=sum(hit))
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / grouped_s
