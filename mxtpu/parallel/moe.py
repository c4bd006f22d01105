"""Expert parallelism: Mixture-of-Experts with capacity-based dispatch.

Beyond the reference's scope (MXNet 1.1 has no MoE) but required of a
complete TPU framework: the ``expert`` mesh axis shards expert weights,
and the einsum-based dispatch/combine below is the GSPMD idiom — under a
global jit with expert-sharded weights, XLA lowers the dispatch einsums to
all-to-alls over ICI automatically (no hand-written collectives), exactly
how Mesh-TF / Switch Transformer formulated it.

Top-1 (Switch) and top-2 routing with capacity factor, load-balancing
auxiliary loss, fully differentiable.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .mesh import MeshContext, ShardingRules, PartitionSpec, AXIS_EXPERT

__all__ = ["moe_dispatch", "moe_ffn", "expert_sharding_rules",
           "route_topk", "held_assignments", "grouped_matmul",
           "held_window", "moe_ffn_held"]


def moe_dispatch(gate_logits, capacity, num_selected=1):
    """Compute dispatch/combine tensors for capacity-C routing.

    gate_logits: [T, E]. Returns (dispatch [T, E, C] one-hot,
    combine [T, E, C] gate-weighted, aux_loss scalar).
    """
    t, e = gate_logits.shape
    probs = jax.nn.softmax(gate_logits, axis=-1)

    dispatch = jnp.zeros((t, e, capacity), probs.dtype)
    combine = jnp.zeros((t, e, capacity), probs.dtype)
    remaining = probs
    fill = jnp.zeros((e,), jnp.int32)
    for _ in range(num_selected):
        idx = jnp.argmax(remaining, axis=-1)                 # [T]
        onehot = jax.nn.one_hot(idx, e, dtype=probs.dtype)   # [T, E]
        # position of each token within its expert's capacity
        pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot    # [T, E]
        pos = pos + fill[None, :].astype(probs.dtype) * onehot
        keep = (pos < capacity) & (onehot > 0)
        pos_i = jnp.clip(pos.astype(jnp.int32), 0, capacity - 1)
        cap_onehot = jax.nn.one_hot(pos_i, capacity,
                                    dtype=probs.dtype)        # [T, E, C]
        sel = keep.astype(probs.dtype)[..., None] * cap_onehot
        dispatch = dispatch + sel
        gate = (remaining * onehot).sum(-1)                  # [T]
        combine = combine + sel * gate[:, None, None]
        fill = fill + jnp.sum(keep, axis=0).astype(jnp.int32)
        remaining = remaining * (1.0 - onehot)

    # Switch load-balancing loss: E * sum_e fraction_tokens_e * mean_prob_e
    top1 = jax.nn.one_hot(jnp.argmax(probs, -1), e, dtype=probs.dtype)
    frac = top1.mean(axis=0)
    mean_prob = probs.mean(axis=0)
    aux = e * jnp.sum(frac * mean_prob)
    return dispatch, combine, aux


def moe_ffn(x, gate_w, w1, b1, w2, b2, capacity_factor=1.25,
            num_selected=1):
    """Expert feed-forward layer.

    x [T, D]; gate_w [D, E]; w1 [E, D, H]; b1 [E, H]; w2 [E, H, D];
    b2 [E, D]. With w1/w2/b1/b2 sharded over the ``expert`` axis the
    ecd/ech einsums become the expert all-to-all. Returns (y [T, D],
    aux_loss)."""
    t, d = x.shape
    e = gate_w.shape[1]
    capacity = max(1, int(math.ceil(t / e * capacity_factor))
                   * num_selected)
    logits = x @ gate_w
    dispatch, combine, aux = moe_dispatch(logits, capacity, num_selected)
    expert_in = jnp.einsum("tec,td->ecd", dispatch, x)
    h = jax.nn.relu(jnp.einsum("ecd,edh->ech", expert_in, w1)
                    + b1[:, None, :])
    out_e = jnp.einsum("ech,ehd->ecd", h, w2) + b2[:, None, :]
    y = jnp.einsum("tec,ecd->td", combine, out_e)
    return y, aux


def expert_sharding_rules(extra=None):
    """ShardingRules placing MoE expert weights on the ``expert`` axis
    (first dim = expert index), composable with user TP rules."""
    rules = [
        (r".*moe.*_w[12]$", PartitionSpec(AXIS_EXPERT)),
        (r".*moe.*_b[12]$", PartitionSpec(AXIS_EXPERT)),
        (r".*expert.*weight", PartitionSpec(AXIS_EXPERT)),
    ]
    return ShardingRules(rules + list(extra or []))


# ---------------------------------------------------------------------------
# Top-k routing over ALL experts, computed on the experts held HERE.
#
# What a large sparse decoder's expert layer asks for, and what expert
# parallelism asks of every device: a router as wide as the model has
# experts, and a device that is told which of them it holds
# (``expert_first .. expert_first + experts_held - 1``) and adds their part
# of the result. No capacity, so no token is dropped and no [T, E, C]
# tensor exists: the assignments that fall on held experts are sorted by
# expert and go through one grouped matrix product per weight.
# ---------------------------------------------------------------------------

def route_topk(x, router_w, select_bias, top_k, scale=1.0, scoring="sigmoid"):
    """``x [N, D]``, ``router_w [E, D]``, ``select_bias [E]``. Scores are
    ``scoring`` of the logits ``x router_w^T`` in float32: their
    ``"sigmoid"``, each expert's own, or their ``"softmax"`` over all ``E``
    experts; the
    ``top_k`` experts with the largest ``score + select_bias`` are chosen
    (the bias only selects); their scores, renormalised to sum to one and
    times ``scale``, are the weights. Returns ``(experts [N, k] int32,
    weights [N, k] float32)``."""
    logits = jnp.einsum("nd,ed->ne", x, router_w,
                        preferred_element_type=jnp.float32)
    if scoring == "sigmoid":
        s = jax.nn.sigmoid(logits)
    elif scoring == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError("route_topk: scoring %r is neither 'sigmoid' nor "
                         "'softmax'" % (scoring,))
    _top, experts = jax.lax.top_k(s + select_bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, experts, axis=1)
    w = scale * w / jnp.sum(w, axis=1, keepdims=True)
    return experts.astype(jnp.int32), w


def held_assignments(experts, experts_held, expert_first, rows=None):
    """Sort the assignments ``experts [N, k]`` by held expert. Returns
    ``(order [N k], sizes [experts_held], n_held)``: ``order`` lists the
    flat assignments (token ``i // k``) with those on held experts first,
    grouped by expert; ``sizes`` counts each held expert's. ``rows [N]``
    (bool): only these tokens' assignments count as held (padding is
    nobody's)."""
    local = experts.reshape(-1) - expert_first
    held = (local >= 0) & (local < experts_held)
    if rows is not None:
        held &= jnp.repeat(rows, experts.shape[1])
    key = jnp.where(held, local, experts_held)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(key[:, None] == jnp.arange(experts_held)[None, :],
                    axis=0, dtype=jnp.int32)
    return order, sizes, jnp.sum(sizes)


# rows, contraction and columns of a tile of the grouped product: timed on
# the v5e at the widths of a 6144 x 2048 expert (PERF.md, PR 30)
GROUPED_TILING = (128, 512, 2048)


def grouped_matmul(rows, w, sizes, tile_rows=GROUPED_TILING[0]):
    """``rows [M, K]``, sorted by group, times ``w [G, K, N]``: the rows of
    group ``i`` (``sizes[i]`` of them, in order) against ``w[i]``, float32
    out. Rows past ``sum(sizes)`` come back undefined. The Pallas kernel is
    JAX's own ``megablox.gmm``: a tile of ``tile_rows`` rows visits only the
    groups it holds rows of, so each held expert's matrix is read about
    once a call whatever the number of rows."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    from ..ops.pallas_util import per_platform
    tiling = tuple(min(t, n) for t, n in zip(
        (tile_rows,) + GROUPED_TILING[1:],
        (rows.shape[0], rows.shape[1], w.shape[2])))
    return per_platform(
        lambda interpret: functools.partial(
            gmm, preferred_element_type=jnp.float32, tiling=tiling,
            interpret=interpret), rows, w, sizes)


# A chunk's held rows are computed in windows of the sorted order where one
# window, the share of the assignments this device expects (``held / E``)
# with this margin over it, spares at least so many rows of the one pass
# over all of them: timed on the v5e (PERF.md, PR 37)
HELD_WINDOW_MARGIN = 1.25
HELD_WINDOW_MIN_SPARED = 512


def held_window(n_assign, held, n_experts):
    """How :func:`moe_ffn_held` computes ``n_assign`` assignments of which
    the ``held`` of ``n_experts`` experts' are this device's: ``(rows of a
    window, rows of a tile of the grouped product)`` where it walks the
    held rows in windows, ``None`` where it makes one pass over all rows (a
    decode step's few tiles; every expert held). From shapes alone."""
    if held >= n_experts:
        return None
    rows = math.ceil(n_assign * held / n_experts * HELD_WINDOW_MARGIN)
    # from 1,024 rows on, tiles of twice the rows: an expert's matrices are
    # visited by fewer tiles (timed at 128, 256 and 512; PERF.md, PR 37)
    tile = GROUPED_TILING[0] * (2 if rows >= 1024 else 1)
    window = -(-rows // tile) * tile
    if n_assign - window < HELD_WINDOW_MIN_SPARED:
        return None
    return window, tile


def _held_rows(x, weights, w_gate, w_up, w_down, top_k, tile, order, sizes,
               n_live):
    """The assignments ``order [M]`` (whole tiles; the first ``n_live``
    held, grouped by expert as ``sizes`` says) through their experts:
    ``(token [M], out [M, D] float32)``, each row weighed, the rows past
    ``n_live`` zero."""
    token = order // top_k
    rows = jnp.take(x, token, axis=0)                        # [M, D]
    h = (jax.nn.silu(grouped_matmul(rows, w_gate, sizes, tile))
         * grouped_matmul(rows, w_up, sizes, tile))
    out = grouped_matmul(h.astype(x.dtype), w_down, sizes, tile)
    w_sorted = jnp.take(weights.reshape(-1), order)
    live = jnp.arange(order.shape[0]) < n_live
    return token, jnp.where(live[:, None], out * w_sorted[:, None], 0.0)


def moe_ffn_held(x, router_w, select_bias, w_gate, w_up, w_down, top_k,
                 expert_first=0, scale=1.0, rows=None, scoring="sigmoid"):
    """The held experts' part of a SiLU-gated expert layer.

    ``x [N, D]``; ``router_w [E, D]`` over ALL ``E`` experts;
    ``w_gate, w_up [held, D, F]``, ``w_down [held, F, D]`` the experts
    ``expert_first .. expert_first + held - 1``. Returns ``(y [N, D], load
    [held + 1] int32)``: ``y = sum over a token's chosen experts that are
    held of weight x expert(x)``, ``expert(x) = (silu(x Wg) * (x Wu)) Wd``;
    ``load`` counts the assignments of each held expert and, last, all
    of them. A token none of whose experts is held gets zeros: what the
    other devices' experts add is theirs to add. ``rows [N]`` (bool) marks
    the tokens that are real; the others (a padded chunk's tail) are
    neither computed nor counted. ``scoring`` is :func:`route_topk`'s.

    The assignments are sorted, the held ones first and grouped by expert.
    Where :func:`held_window` gives a window, the held rows are gathered,
    multiplied, weighed and added back a window of the sorted order at a
    time, in as many trips as the held rows need (none held: no trip; all
    on one held expert: ``N k`` rows over the window trips; no capacity
    either way); else in one pass over all ``N k`` rows."""
    n, d = x.shape
    held = w_gate.shape[0]
    experts, weights = route_topk(x, router_w, select_bias, top_k, scale,
                                  scoring)
    order, sizes, n_held = held_assignments(experts, held, expert_first,
                                            rows)
    n_rows = n if rows is None else jnp.sum(rows, dtype=jnp.int32)
    through = functools.partial(_held_rows, x, weights, w_gate, w_up, w_down,
                                top_k)
    walk = held_window(order.shape[0], held, router_w.shape[0])
    if walk is None:
        # whole tiles of rows: the padding lies past the held rows, in no
        # group
        tile = min(GROUPED_TILING[0], -(-order.shape[0] // 8) * 8)
        order = jnp.pad(order, (0, -order.shape[0] % tile))
        token, out = through(tile, order, sizes, n_held)
        y = jnp.zeros((n, d), jnp.float32).at[token].add(out)
    else:
        window, tile = walk
        order = jnp.pad(order, (0, -order.shape[0] % window))
        ends = jnp.cumsum(sizes)

        def add_window(i, y):
            lo = i * window
            # the part of each held expert's rows that lies in the window
            cut = jnp.clip(ends, lo, lo + window) \
                - jnp.clip(ends - sizes, lo, lo + window)
            token, out = through(
                tile, jax.lax.dynamic_slice(order, (lo,), (window,)), cut,
                n_held - lo)
            return y.at[token].add(out)

        y = jax.lax.fori_loop(0, -(-n_held // window), add_window,
                              jnp.zeros((n, d), jnp.float32))
    load = jnp.concatenate([sizes, jnp.reshape(n_rows * top_k, (1,))
                            .astype(jnp.int32)])
    return y.astype(x.dtype), load
