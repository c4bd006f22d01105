"""Fused RNN op.

Capability parity with MXNet's fused RNN operator
(``src/operator/rnn-inl.h``, ``src/operator/cudnn_rnn-inl.h``): one op runs
a full multi-layer (optionally bidirectional) RNN/LSTM/GRU over a sequence,
with all weights packed into a single flat parameter vector exactly like
the cuDNN packing the reference uses.

TPU-first design: the time loop is a ``lax.scan`` (compiled once, no
per-step dispatch), the per-step math is two MXU matmuls batched over the
whole layer, and dropout between layers draws from the functional PRNG.
Gate orders: LSTM [i, f, g, o]; GRU [r, z, n] — consistent with the
unfused cells in gluon/rnn/rnn_cell.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register, next_rng_key

_GATES = {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}

# LSTM/GRU time-loop backend: lax.scan unless set to True, which routes
# through the Pallas kernels of ops/pallas_rnn.py (they hold only hidden
# sizes whose recurrent weight fits VMEM, and no measurement on the
# current machine says they are faster). Read at TRACE time — set it
# before the first forward of a model; already-jit-cached traces keep
# whichever backend they were traced with.
USE_PALLAS_RNN = False


def rnn_blob_blocks(mode, input_size, state_size, num_layers, num_dir):
    """The ONE definition of the flat cudnn-layout blob: all weights
    (layer-major, direction within layer), then all biases. Yields
    per-(layer, direction) block offsets/shapes consumed both by the op
    (``_unpack_params``) and by ``FusedRNNCell.unpack_weights``
    (rnn/rnn_cell.py) so the two can never drift."""
    G = _GATES[mode]
    H = state_size
    blocks = []
    off = 0
    for layer in range(num_layers):
        isz = input_size if layer == 0 else H * num_dir
        for d in range(num_dir):
            blocks.append({"layer": layer, "dir": d,
                           "wi": (off, (G * H, isz)),
                           "wh": (off + G * H * isz, (G * H, H))})
            off += G * H * isz + G * H * H
    i = 0
    for layer in range(num_layers):
        for d in range(num_dir):
            blocks[i]["bi"] = (off, (G * H,))
            blocks[i]["bh"] = (off + G * H, (G * H,))
            off += 2 * G * H
            i += 1
    return blocks, off


def _unpack_params(params, mode, input_size, state_size, num_layers,
                   num_dir):
    """Slice the flat cudnn-layout vector per rnn_blob_blocks."""
    blocks, _ = rnn_blob_blocks(mode, input_size, state_size, num_layers,
                                num_dir)
    weights, biases = [], []
    for b in blocks:
        (wi_off, wi_shape), (wh_off, wh_shape) = b["wi"], b["wh"]
        wi = params[wi_off:wi_off + wi_shape[0] * wi_shape[1]] \
            .reshape(wi_shape)
        wh = params[wh_off:wh_off + wh_shape[0] * wh_shape[1]] \
            .reshape(wh_shape)
        weights.append((wi, wh))
        (bi_off, bi_shape), (bh_off, bh_shape) = b["bi"], b["bh"]
        biases.append((params[bi_off:bi_off + bi_shape[0]],
                       params[bh_off:bh_off + bh_shape[0]]))
    return weights, biases


def rnn_param_size(mode, input_size, state_size, num_layers, bidirectional):
    _, size = rnn_blob_blocks(mode, input_size, state_size, num_layers,
                              2 if bidirectional else 1)
    return size


def _cell_step(mode, H):
    if mode in ("rnn_relu", "rnn_tanh"):
        act = jnp.tanh if mode == "rnn_tanh" else lambda v: jnp.maximum(v, 0)

        def step(carry, gates):
            h, c = carry
            new_h = act(gates)
            return (new_h, c), new_h
    elif mode == "lstm":
        def step(carry, gates):
            h, c = carry
            i, f, g, o = jnp.split(gates, 4, axis=-1)
            i = jax.nn.sigmoid(i)
            f = jax.nn.sigmoid(f)
            g = jnp.tanh(g)
            o = jax.nn.sigmoid(o)
            new_c = f * c + i * g
            new_h = o * jnp.tanh(new_c)
            return (new_h, new_c), new_h
    else:
        step = None  # gru handled separately (needs h inside gate math)
    return step


def _run_direction(xs, h0, c0, wi, wh, bi, bh, mode, reverse):
    """xs: (T, N, I); returns (T, N, H), hT, cT."""
    H = h0.shape[-1]
    G = _GATES[mode]
    # hoist the input projection out of the scan: one big MXU matmul
    x_proj = jnp.einsum("tni,gi->tng", xs, wi) + bi  # (T, N, G*H)
    if reverse:
        x_proj = jnp.flip(x_proj, axis=0)

    if mode == "gru":
        # split h2h so the candidate gate sees r * (h @ Whn + bhn)
        wh_rz, wh_n = wh[:2 * H], wh[2 * H:]
        bh_rz, bh_n = bh[:2 * H], bh[2 * H:]
        if USE_PALLAS_RNN:
            from .pallas_rnn import gru_scan
            # fold the r/z recurrent bias into the hoisted projection
            xp = x_proj.at[:, :, :2 * H].add(bh_rz)
            ys, hT = gru_scan(xp, h0, wh_rz.T, wh_n.T, bh_n)
            if reverse:
                ys = jnp.flip(ys, axis=0)
            return ys, hT, hT

        def step(carry, xp):
            h, _ = carry
            rz = jax.nn.sigmoid(
                xp[:, :2 * H] + h @ wh_rz.T + bh_rz)
            r, z = jnp.split(rz, 2, axis=-1)
            n = jnp.tanh(xp[:, 2 * H:] + r * (h @ wh_n.T + bh_n))
            new_h = (1 - z) * n + z * h
            return (new_h, new_h), new_h
    elif mode == "lstm" and USE_PALLAS_RNN:
        from .pallas_rnn import lstm_scan
        ys, hT, cT = lstm_scan(x_proj + bh, h0, c0, wh.T)
        if reverse:
            ys = jnp.flip(ys, axis=0)
        return ys, hT, cT
    else:
        cell = _cell_step(mode, H)

        def step(carry, xp):
            h, c = carry
            gates = xp + h @ wh.T + bh
            return cell((h, c), gates)

    (hT, cT), ys = lax.scan(step, (h0, c0), x_proj)
    if reverse:
        ys = jnp.flip(ys, axis=0)
    return ys, hT, cT


@register("RNN", aliases=("rnn",), stateful=True, needs_train_flag=True)
def rnn(data, parameters, state, state_cell=None, state_size=0,
        num_layers=1, bidirectional=False, mode="lstm", p=0.0,
        state_outputs=False, lstm_state_clip_min=None,
        lstm_state_clip_max=None, _training=False):
    """data: (T, N, I); state: (L*D, N, H); returns output (T, N, D*H)
    plus final states when state_outputs (reference rnn-inl.h RNNParam)."""
    T, N, I = data.shape
    H = state_size
    D = 2 if bidirectional else 1
    L = num_layers
    weights, biases = _unpack_params(parameters, mode, I, H, L, D)
    if state_cell is None:
        state_cell = jnp.zeros_like(state)
    x = data
    h_finals, c_finals = [], []
    for layer in range(L):
        outs = []
        for d in range(D):
            idx = layer * D + d
            wi, wh = weights[idx]
            bi, bh = biases[idx]
            ys, hT, cT = _run_direction(
                x, state[idx], state_cell[idx], wi, wh, bi, bh, mode,
                reverse=(d == 1))
            outs.append(ys)
            h_finals.append(hT)
            c_finals.append(cT)
        x = outs[0] if D == 1 else jnp.concatenate(outs, axis=-1)
        if p > 0.0 and _training and layer != L - 1:
            keep = jax.random.bernoulli(next_rng_key(), 1.0 - p, x.shape)
            x = jnp.where(keep, x / (1.0 - p), 0.0)
    h_out = jnp.stack(h_finals, axis=0)
    if mode == "lstm":
        c_out = jnp.stack(c_finals, axis=0)
        if lstm_state_clip_min is not None:
            c_out = jnp.clip(c_out, lstm_state_clip_min, lstm_state_clip_max)
        if state_outputs:
            return x, h_out, c_out
        return x
    if state_outputs:
        return x, h_out
    return x
