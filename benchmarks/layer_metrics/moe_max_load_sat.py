"""``moe.max_load.sat``: the fullest held expert's assignments over the mean
of the held experts' (1 is even), in the layer where that reads highest: how
long the grouped product's longest group is against its average. From the
registry gauge ``ops.moe_ffn.max_load``, which the engine fills from what
its device sums gained between the scheduler's two ``stats()`` readings at
the window's ends."""
from .moe_held_share_sat import registry


def read(run, trace):
    loads = [v for v in registry("ops.moe_ffn.max_load") if v]
    return max(loads) if loads else None
