"""Plain float32 reference of the pre-activation ResNet (He et al.,
arXiv:1603.05027 form of the arXiv:1512.03385 table-1 plans) as MXNet's
``example/image-classification/symbols/resnet.py`` writes it: BN on the data,
7x7/2 stem, 3x3/2 max pool, four stages of bottleneck (or basic) units with
the stride on the 3x3 convolution, BN-ReLU, global average pool, FC, softmax
cross-entropy, SGD with momentum.

Straight ``jax.numpy``/``lax`` in float32 at ``Precision.HIGHEST``; imports
nothing of ``mxtpu``. It also owns the weights: ``init_state`` makes them from
the seed, and the program is handed the same arrays.

Departures, each for memory only: the pass runs layer by layer, every
residual unit a jitted program of its own whose backward pass recomputes the
unit from its kept input, so that batch 128 at 224x224 fits one chip in
float32 (the arithmetic is unchanged: a unit's forward is computed twice).

``held`` puts the reference in the program's place at a lower precision:
wherever the program holds a tensor in bfloat16 (the weights it computes with,
every activation between two operations) ``"fp8"``, the control one precision
down, holds it in scaled float8, e4m3 on the way forward and e5m2 for its
gradient, and ``"bf16"``, the witness at the precision the configuration
states, in bfloat16 both ways; BatchNorm statistics, master weights and the
optimizer stay in float32, as in the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..common import seed_key

PLANS = {18: ((2, 2, 2, 2), False), 34: ((3, 4, 6, 3), False),
         50: ((3, 4, 6, 3), True), 101: ((3, 4, 23, 3), True),
         152: ((3, 8, 36, 3), True)}
BN_EPS = 2e-5
HI = lax.Precision.HIGHEST


def plan(cfg):
    units, bottleneck = PLANS[int(cfg["num_layers"])]
    filters = (256, 512, 1024, 2048) if bottleneck else (64, 128, 256, 512)
    return units, filters, bottleneck


def layout(cfg):
    """Every leaf of the model: ``(params, aux, convs)``. ``params`` and
    ``aux`` list ``(name, shape, kind)`` with kind conv, fc, zero or one;
    ``convs`` lists ``(weight name, output height)`` for the count of
    multiply-adds."""
    c, h = int(cfg["image_shape"][0]), int(cfg["image_shape"][1])
    units, filters, bottleneck = plan(cfg)
    params, aux, convs = [], [], []

    def bn(name, ch):
        params.append((name + "_gamma", (ch,), "one"))
        params.append((name + "_beta", (ch,), "zero"))
        aux.append((name + "_moving_mean", (ch,), "zero"))
        aux.append((name + "_moving_var", (ch,), "one"))

    def conv(name, out, cin, k, out_h):
        params.append((name + "_weight", (out, cin, k, k), "conv"))
        convs.append((name + "_weight", out_h))

    bn("bn_data", c)
    h = (h + 2 * 3 - 7) // 2 + 1
    conv("conv0", 64, c, 7, h)
    bn("bn0", 64)
    h = (h + 2 - 3) // 2 + 1
    cin = 64
    for stage, (n, cout) in enumerate(zip(units, filters)):
        for unit in range(1, n + 1):
            name = "stage%d_unit%d" % (stage + 1, unit)
            stride = 2 if (unit == 1 and stage > 0) else 1
            h_out = (h - 1) // stride + 1
            mid = cout // 4
            bn(name + "_bn1", cin)
            if bottleneck:
                conv(name + "_conv1", mid, cin, 1, h)
                bn(name + "_bn2", mid)
                conv(name + "_conv2", mid, mid, 3, h_out)
                bn(name + "_bn3", mid)
                conv(name + "_conv3", cout, mid, 1, h_out)
            else:
                conv(name + "_conv1", cout, cin, 3, h_out)
                bn(name + "_bn2", cout)
                conv(name + "_conv2", cout, cout, 3, h_out)
            if unit == 1:
                conv(name + "_sc", cout, cin, 1, h_out)
            cin, h = cout, h_out
    bn("bn1", cin)
    ncls = int(cfg["num_classes"])
    params.append(("fc1_weight", (ncls, cin), "fc"))
    params.append(("fc1_bias", (ncls,), "zero"))
    return params, aux, convs


def macs_per_image(cfg):
    """Multiply-adds of one forward pass of one image: every convolution
    (output positions x weight elements) and the FC. An operation count is
    twice this; a training step is three forward passes' worth."""
    params, _aux, convs = layout(cfg)
    shapes = {n: s for n, s, _k in params}
    total = sum(h * h * int(np.prod(shapes[n])) for n, h in convs)
    return total + int(np.prod(shapes["fc1_weight"]))



def init_state(cfg, seed, batch):
    """Weights, BN statistics and the one synthetic batch, all made on the
    device in one jitted call from the seed. Convolutions and the FC draw
    N(0, 2/fan_in) (``Xavier(gaussian, in, 2)``, fit.py's default); gammas 1,
    betas and biases 0. Images are uniform in [-1, 1), labels uniform classes,
    every row its own draw."""
    p_layout, a_layout, _convs = layout(cfg)
    shape = (int(batch),) + tuple(int(x) for x in cfg["image_shape"])
    ncls = int(cfg["num_classes"])

    @jax.jit
    def make(key):
        def leaf(i, shp, kind):
            if kind in ("conv", "fc"):
                fan_in = int(np.prod(shp[1:]))
                return jax.random.normal(jax.random.fold_in(key, i), shp,
                                         jnp.float32) * np.sqrt(2.0 / fan_in)
            return (jnp.ones if kind == "one" else jnp.zeros)(shp, jnp.float32)
        params = {n: leaf(i, s, k) for i, (n, s, k) in enumerate(p_layout)}
        aux = {n: leaf(0, s, k) for n, s, k in a_layout}
        kd, kl = jax.random.split(jax.random.fold_in(key, 1 << 20))
        data = jax.random.uniform(kd, shape, jnp.float32, -1.0, 1.0)
        label = jax.random.randint(kl, shape[:1], 0, ncls).astype(jnp.float32)
        return params, aux, data, label

    return make(seed_key(seed))


# -- forward ------------------------------------------------------------------

def _scaled_round(x, dtype, top):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(x.dtype) * scale


@jax.custom_vjp
def _fp8(x):
    """Float8 with one scale per tensor, as a training recipe in that
    precision has it: the value rounded to e4m3 on the way forward, its
    gradient to e5m2 on the way back."""
    return _scaled_round(x, jnp.float8_e4m3fn, 448.0)


_fp8.defvjp(lambda x: (_fp8(x), None),
            lambda _res, g: (_scaled_round(g, jnp.float8_e5m2, 57344.0),))


def _held(x, held):
    """A tensor as it is held between two operations (its gradient too)."""
    if held == "fp8":
        return _fp8(x)
    if held == "bf16":
        return x.astype(jnp.bfloat16).astype(x.dtype)
    return x


def _conv(x, w, stride, pad, held):
    y = lax.conv_general_dilated(
        x, _held(w, held), (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=HI)
    return _held(y, held)


def _bn_relu(x, p, name, relu=True, held=None):
    """Training-mode BatchNorm: the batch's own mean and biased variance."""
    mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
    var = jnp.var(x, axis=(0, 2, 3), keepdims=True)
    y = (x - mean) * lax.rsqrt(var + BN_EPS)
    y = y * p[name + "_gamma"].reshape(1, -1, 1, 1) \
        + p[name + "_beta"].reshape(1, -1, 1, 1)
    return _held(jnp.maximum(y, 0) if relu else y, held)


def _stem(p, data, held):
    x = _bn_relu(_held(data, held), p, "bn_data", relu=False, held=held)
    x = _conv(x, p["conv0_weight"], 2, 3, held)
    x = _bn_relu(x, p, "bn0", held=held)
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                             [(0, 0), (0, 0), (1, 1), (1, 1)])


def _unit(p, x, stride, dim_match, bottleneck, held):
    """One pre-activation residual unit; ``p`` holds its leaves without the
    unit's prefix."""
    act1 = _bn_relu(x, p, "bn1", held=held)
    if bottleneck:
        y = _conv(act1, p["conv1_weight"], 1, 0, held)
        y = _bn_relu(y, p, "bn2", held=held)
        y = _conv(y, p["conv2_weight"], stride, 1, held)
        y = _bn_relu(y, p, "bn3", held=held)
        y = _conv(y, p["conv3_weight"], 1, 0, held)
    else:
        y = _conv(act1, p["conv1_weight"], stride, 1, held)
        y = _bn_relu(y, p, "bn2", held=held)
        y = _conv(y, p["conv2_weight"], 1, 1, held)
    sc = x if dim_match else _conv(act1, p["sc_weight"], stride, 0, held)
    return _held(y + sc, held)


def _head_rows(p, x, label, held):
    """Softmax cross-entropy of every row."""
    x = _bn_relu(x, p, "bn1", held=held)
    x = _held(jnp.mean(x, axis=(2, 3)), held)
    lg = jnp.dot(x, _held(p["fc1_weight"], held).T, precision=HI) \
        + p["fc1_bias"]
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.take_along_axis(logp, label.astype(jnp.int32)[:, None], 1)[:, 0]


@functools.lru_cache(maxsize=None)
def _programs(held):
    """The reference's jitted pieces. The pass runs layer by layer, each
    residual unit a program of its own (one compile for each distinct shape),
    so that float32 at batch 128 fits the chip and no compiled program is
    large: a unit's input is kept, and its backward pass recomputes the
    unit."""
    static = ("stride", "dim_match", "bottleneck")

    def unit(p, x, stride, dim_match, bottleneck):
        return _unit(p, x, stride, dim_match, bottleneck, held)

    def unit_bwd(p, x, dy, stride, dim_match, bottleneck):
        _y, pull = jax.vjp(lambda p, x: unit(p, x, stride, dim_match,
                                             bottleneck), p, x)
        return pull(dy)

    def stem_bwd(p, data, dy):
        _y, pull = jax.vjp(lambda p: _stem(p, data, held), p)
        return pull(dy)[0]

    def head(p, x, label):
        def mean_loss(p, x):
            rows = _head_rows(p, x, label, held)
            return jnp.mean(rows), rows
        return jax.value_and_grad(mean_loss, argnums=(0, 1), has_aux=True)(p, x)

    return {"stem": jax.jit(lambda p, data: _stem(p, data, held)),
            "stem_bwd": jax.jit(stem_bwd),
            "unit": jax.jit(unit, static_argnames=static),
            "unit_bwd": jax.jit(unit_bwd, static_argnames=static),
            "head": jax.jit(head)}


STEM = ("bn_data_", "conv0_", "bn0_")
HEAD = ("bn1_", "fc1_")


def loss_and_grads(cfg, params, data, label, held=None):
    """``(mean loss, per-row losses, gradients)`` of the mean softmax
    cross-entropy over the rows given."""
    fns = _programs(held)
    units, _filters, bottleneck = plan(cfg)
    stem_p = {k: v for k, v in params.items() if k.startswith(STEM)}
    head_p = {k: v for k, v in params.items() if k.startswith(HEAD)}
    steps = []
    for stage, n in enumerate(units):
        for unit in range(1, n + 1):
            name = "stage%d_unit%d_" % (stage + 1, unit)
            steps.append((name, {k[len(name):]: v for k, v in params.items()
                                 if k.startswith(name)},
                          dict(stride=2 if (unit == 1 and stage > 0) else 1,
                               dim_match=unit > 1, bottleneck=bottleneck)))
    x = fns["stem"](stem_p, data)
    kept = []
    for _name, p, static in steps:
        kept.append(x)
        x = fns["unit"](p, x, **static)
    (mean, rows), (grads, dx) = fns["head"](head_p, x, label)
    grads = dict(grads)
    for (name, p, static), x_in in zip(reversed(steps), reversed(kept)):
        dp, dx = fns["unit_bwd"](p, x_in, dx, **static)
        grads.update({name + k: v for k, v in dp.items()})
    grads.update(fns["stem_bwd"](stem_p, data, dx))
    return mean, rows, grads


def weight_decay_of(name, wd):
    """The optimizer decays ``*_weight`` and ``*_bias`` and nothing else."""
    return wd if name.endswith(("_weight", "_bias")) else 0.0


@functools.partial(jax.jit, static_argnames=("momentum", "lr", "wd"))
def _update(p, g, m, momentum, lr, wd):
    new_m = {k: momentum * m[k] - lr * (g[k] + weight_decay_of(k, wd) * p[k])
             for k in p}
    return {k: p[k] + new_m[k] for k in p}, new_m


def follow(cfg, params, data, label, steps, hp, held=None, rows=None):
    """Follow the first ``steps`` SGD-with-momentum steps on the one batch.
    Returns ``(mean losses, per-row losses, params after each step)``;
    ``rows`` keeps only the first rows of the batch (the half-batch fault)."""
    if rows is not None:
        data, label = data[:rows], label[:rows]
    mom = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, row_losses, after = [], [], []
    for _ in range(steps):
        mean, per_row, g = loss_and_grads(cfg, params, data, label, held)
        params, mom = _update(params, g, mom, hp["momentum"], hp["lr"],
                              hp["wd"])
        losses.append(float(mean))
        row_losses.append(np.asarray(per_row, np.float64))
        after.append(params)
    return losses, row_losses, after
