"""``moe.held_share.sat``: of all the token-to-expert assignments the expert
layers routed in this process, the share that fell on experts this chip
holds (16 of 128 held: 12.5% under even routing). From the registry counters
``ops.moe_ffn.assignments`` and ``ops.moe_ffn.assignments_held``, which the
engine fills from its device sums; a program without them reads nothing.
(The manifest wants a direction and has "higher"; the share has none: a
reading away from 12.5 either way means the routing is uneven.)"""


def registry(name):
    """The values of a registry metric's series ([] where the program has
    no such metric)."""
    try:
        from mxtpu import obs
    except ImportError:
        return []
    family = obs.REGISTRY.snapshot()["metrics"].get(name, {})
    return list(family.get("series", {}).values())


def read(run, trace):
    total = sum(registry("ops.moe_ffn.assignments"))
    held = sum(registry("ops.moe_ffn.assignments_held"))
    if not total or not held:
        return None
    return 100.0 * held / total
