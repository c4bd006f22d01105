"""Neural-network ops.

Capability parity with ``src/operator/nn/*`` (Convolution, FullyConnected,
BatchNorm, Pooling, Activation, softmax, Dropout, LRN, Embedding, UpSampling,
...) and the loss/output heads (SoftmaxOutput etc., which in MXNet carry
custom backward semantics — rendered here with ``jax.custom_vjp``).

TPU notes: matmuls/convs hit the MXU through lax.dot_general /
lax.conv_general_dilated; XLA fuses the elementwise tails. Layout is NCHW at
the API (MXNet default) — XLA re-layouts internally for TPU.
"""
from __future__ import annotations

import collections
import functools
import os

import jax
import jax.numpy as jnp
from jax import lax

from .. import obs as _obs
from .registry import register, next_rng_key


def _nhwc_enabled():
    """MXTPU_CONV_LAYOUT=NHWC: run 2-D conv/pool internally channels-last.

    TPU systolic/vector units natively prefer channels-last; with the flag
    set, each conv/pool transposes NCHW->NHWC at entry and back at exit.
    Adjacent pairs cancel in XLA's algebraic simplifier (and elementwise
    ops commute through), so a conv-net chain effectively runs NHWC end to
    end while the public API stays NCHW (MXNet default). Read at trace
    time."""
    return os.environ.get("MXTPU_CONV_LAYOUT", "").upper() == "NHWC"

# ---------------------------------------------------------------------------
# FullyConnected (reference: src/operator/nn/fully_connected-inl.h:103-165,
# cuBLAS linalg_gemm there; one dot_general on the MXU here).
# ---------------------------------------------------------------------------

@register("FullyConnected", aliases=("fully_connected",))
def fully_connected(data, weight, bias=None, num_hidden=0, no_bias=False,
                    flatten=True):
    if flatten:
        x = data.reshape(data.shape[0], -1)
    else:
        x = data
    out = lax.dot_general(x, weight, (((x.ndim - 1,), (1,)), ((), ())),
                          preferred_element_type=jnp.float32
                          if x.dtype == jnp.bfloat16 else None)
    if out.dtype != x.dtype:
        out = out.astype(x.dtype)
    if bias is not None and not no_bias:
        out = out + bias
    return out


# ---------------------------------------------------------------------------
# Convolution / Deconvolution
# ---------------------------------------------------------------------------

def _pair(v, n=2):
    if isinstance(v, int):
        return (v,) * n
    v = tuple(v)
    return v if v else (1,) * n


def _conv_dims(ndim):
    if ndim == 3:  # NCW
        return ("NCH", "OIH", "NCH")
    if ndim == 4:
        return ("NCHW", "OIHW", "NCHW")
    return ("NCDHW", "OIDHW", "NCDHW")


@register("Convolution", aliases=("convolution",))
def convolution(data, weight, bias=None, kernel=(), stride=(), dilate=(),
                pad=(), num_filter=0, num_group=1, no_bias=False,
                workspace=1024, cudnn_tune=None, cudnn_off=False, layout=None):
    """NCHW conv on the MXU. Weight layout (num_filter, C/group, *kernel)
    matches the reference (src/operator/nn/convolution-inl.h)."""
    nsp = data.ndim - 2
    stride = _pair(stride, nsp) if stride else (1,) * nsp
    dilate = _pair(dilate, nsp) if dilate else (1,) * nsp
    pad = _pair(pad, nsp) if pad else (0,) * nsp
    nhwc = nsp == 2 and _nhwc_enabled()
    if nhwc:
        data = jnp.transpose(data, (0, 2, 3, 1))
        weight = jnp.transpose(weight, (2, 3, 1, 0))  # OIHW -> HWIO
        dn = ("NHWC", "HWIO", "NHWC")
    else:
        dn = lax.conv_dimension_numbers(data.shape, weight.shape,
                                        _conv_dims(data.ndim))
    out = lax.conv_general_dilated(
        data, weight, window_strides=stride,
        padding=[(p, p) for p in pad], rhs_dilation=dilate,
        dimension_numbers=dn, feature_group_count=num_group)
    if bias is not None and not no_bias:
        out = out + bias.reshape((1, 1, 1, -1) if nhwc
                                 else (1, -1) + (1,) * nsp)
    return jnp.transpose(out, (0, 3, 1, 2)) if nhwc else out


@register("Deconvolution", aliases=("deconvolution",))
def deconvolution(data, weight, bias=None, kernel=(), stride=(), dilate=(),
                  pad=(), adj=(), target_shape=(), num_filter=0, num_group=1,
                  no_bias=True, workspace=512, cudnn_tune=None,
                  cudnn_off=False, layout=None):
    """Transposed conv (reference src/operator/nn/deconvolution-inl.h).
    Weight layout (C_in, num_filter/group, *kernel) as in MXNet."""
    nsp = data.ndim - 2
    stride = _pair(stride, nsp) if stride else (1,) * nsp
    dilate = _pair(dilate, nsp) if dilate else (1,) * nsp
    pad = _pair(pad, nsp) if pad else (0,) * nsp
    adj = _pair(adj, nsp) if adj else (0,) * nsp
    kernel = _pair(kernel, nsp) if kernel else weight.shape[2:]
    if target_shape and any(_pair(target_shape, nsp)):
        # reference InferPad (deconvolution-inl.h:124-141): an explicit
        # target_shape overrides pad AND adj — out = (in-1)*s - 2p
        # + k_eff + adj solved for (p, adj) with adj in {0, 1}. An
        # all-zero target_shape means "unset" (bCal skips it), and a
        # target larger than the zero-pad output is rejected (the
        # reference's CHECK_GE "too big target shape").
        target_shape = _pair(target_shape, nsp)
        pad_l, adj_l = [], []
        for i in range(nsp):
            k_eff = (kernel[i] - 1) * dilate[i] + 1
            excess = (data.shape[2 + i] - 1) * stride[i] + k_eff \
                - target_shape[i]
            if excess < 0:
                raise ValueError(
                    "too big target shape: target_shape[%d]=%d exceeds the "
                    "maximum achievable output %d for input %d, stride %d, "
                    "kernel %d, dilate %d" % (
                        i, target_shape[i],
                        (data.shape[2 + i] - 1) * stride[i] + k_eff,
                        data.shape[2 + i], stride[i], kernel[i], dilate[i]))
            p = (excess + 1) // 2
            pad_l.append(p)
            adj_l.append(2 * p - excess)
        pad, adj = tuple(pad_l), tuple(adj_l)
    # Transposed conv = gradient of conv w.r.t. its input: use
    # conv_general_dilated with lhs_dilation (fractional stride).
    # Flip spatial dims of the kernel and swap in/out channels.
    w = jnp.flip(weight, axis=tuple(range(2, weight.ndim)))
    w = jnp.swapaxes(w, 0, 1)  # (out/group? ...) -> (num_filter/group, C_in, ...)
    # padding for full correlation
    pads = []
    for i in range(nsp):
        k = (kernel[i] - 1) * dilate[i]
        pads.append((k - pad[i], k - pad[i] + adj[i]))
    if num_group > 1:
        # grouped deconv: split channels, run per group, concat
        xs = jnp.split(data, num_group, axis=1)
        ws = jnp.split(w, num_group, axis=0)
        outs = []
        for xg, wg in zip(xs, ws):
            dn = lax.conv_dimension_numbers(xg.shape, wg.shape, _conv_dims(data.ndim))
            outs.append(lax.conv_general_dilated(
                xg, wg, window_strides=(1,) * nsp, padding=pads,
                lhs_dilation=stride, rhs_dilation=dilate, dimension_numbers=dn))
        out = jnp.concatenate(outs, axis=1)
    else:
        dn = lax.conv_dimension_numbers(data.shape, w.shape, _conv_dims(data.ndim))
        out = lax.conv_general_dilated(
            data, w, window_strides=(1,) * nsp, padding=pads,
            lhs_dilation=stride, rhs_dilation=dilate, dimension_numbers=dn)
    if bias is not None and not no_bias:
        out = out + bias.reshape((1, -1) + (1,) * nsp)
    return out


# ---------------------------------------------------------------------------
# Pooling (reference: src/operator/nn/pooling-inl.h) via reduce_window.
# ---------------------------------------------------------------------------

@register("Pooling", aliases=("pooling",))
def pooling(data, kernel=(), pool_type="max", global_pool=False, stride=(),
            pad=(), pooling_convention="valid", cudnn_off=False,
            count_include_pad=True):
    nsp = data.ndim - 2
    if global_pool:
        kernel = data.shape[2:]
        stride = (1,) * nsp
        pad = (0,) * nsp
    else:
        kernel = _pair(kernel, nsp)
        stride = _pair(stride, nsp) if stride else (1,) * nsp
        pad = _pair(pad, nsp) if pad else (0,) * nsp
    nhwc = nsp == 2 and _nhwc_enabled()
    if nhwc:
        data = jnp.transpose(data, (0, 2, 3, 1))
        window = (1,) + tuple(kernel) + (1,)
        strides = (1,) + tuple(stride) + (1,)
    else:
        window = (1, 1) + tuple(kernel)
        strides = (1, 1) + tuple(stride)
    if pooling_convention == "full":
        # ceil-mode: pad high edge enough that ceil division is covered
        sp_pads = []
        for i in range(nsp):
            in_sz = data.shape[(1 if nhwc else 2) + i] + 2 * pad[i]
            out_sz = -(-(in_sz - kernel[i]) // stride[i]) + 1  # ceil
            needed = (out_sz - 1) * stride[i] + kernel[i] - in_sz
            sp_pads.append((pad[i], pad[i] + max(needed, 0)))
    else:
        sp_pads = [(p, p) for p in pad]
    if nhwc:
        pads = [(0, 0)] + sp_pads + [(0, 0)]
    else:
        pads = [(0, 0), (0, 0)] + sp_pads
    def _back(x):
        return jnp.transpose(x, (0, 3, 1, 2)) if nhwc else x

    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) else jnp.iinfo(data.dtype).min
        return _back(lax.reduce_window(data, init, lax.max, window, strides,
                                       pads))
    if pool_type in ("avg", "sum"):
        summed = lax.reduce_window(data, 0.0, lax.add, window, strides, pads)
        if pool_type == "sum":
            return _back(summed)
        if count_include_pad:
            denom = 1.0
            for k in kernel:
                denom *= k
            return _back(summed / denom)
        ones = jnp.ones_like(data)
        counts = lax.reduce_window(ones, 0.0, lax.add, window, strides, pads)
        return _back(summed / counts)
    raise ValueError("unknown pool_type %r" % pool_type)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------

@register("Activation", aliases=("activation",))
def activation(data, act_type="relu"):
    if act_type == "relu":
        return jnp.maximum(data, 0)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(data)
    if act_type == "tanh":
        return jnp.tanh(data)
    if act_type == "softrelu":
        return jnp.logaddexp(data, 0.0)
    if act_type == "softsign":
        return data / (1 + jnp.abs(data))
    if act_type == "silu":
        return jax.nn.silu(data)
    raise ValueError("unknown act_type %r" % act_type)


@register("LeakyReLU", needs_train_flag=True, stateful=True)
def leaky_relu(data, gamma=None, act_type="leaky", slope=0.25,
               lower_bound=0.125, upper_bound=0.334, _training=False):
    if act_type == "leaky":
        return jnp.where(data >= 0, data, slope * data)
    if act_type == "elu":
        return jnp.where(data >= 0, data, slope * jnp.expm1(data))
    if act_type == "selu":
        alpha, scale = 1.6732632423543772, 1.0507009873554805
        return scale * jnp.where(data >= 0, data, alpha * jnp.expm1(data))
    if act_type == "prelu":
        g = gamma.reshape((1, -1) + (1,) * (data.ndim - 2))
        return jnp.where(data >= 0, data, g * data)
    if act_type == "rrelu":
        if _training:
            u = jax.random.uniform(next_rng_key(), data.shape, dtype=data.dtype,
                                   minval=lower_bound, maxval=upper_bound)
            return jnp.where(data >= 0, data, u * data)
        s = (lower_bound + upper_bound) / 2.0
        return jnp.where(data >= 0, data, s * data)
    raise ValueError("unknown act_type %r" % act_type)


# ---------------------------------------------------------------------------
# Softmax family
# ---------------------------------------------------------------------------

@register("softmax")
def softmax(data, axis=-1, temperature=None):
    x = data / temperature if temperature else data
    return jax.nn.softmax(x, axis=axis)


@register("log_softmax")
def log_softmax(data, axis=-1, temperature=None):
    x = data / temperature if temperature else data
    return jax.nn.log_softmax(x, axis=axis)


@register("SoftmaxActivation")
def softmax_activation(data, mode="instance"):
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)


# ---------------------------------------------------------------------------
# Normalisation
# ---------------------------------------------------------------------------

def _bn_stats(data, axis):
    red = tuple(i for i in range(data.ndim) if i != axis)
    mean = jnp.mean(data, axis=red)
    var = jnp.var(data, axis=red)
    return mean, var


@register("BatchNorm", aliases=("batch_norm", "BatchNorm_v1"),
          num_outputs=5,
          user_outputs=lambda p: 3 if p.get("output_mean_var") else 1,
          aux_update={3: 3, 4: 4}, needs_train_flag=True)
def batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-3,
               momentum=0.9, fix_gamma=True, use_global_stats=False,
               output_mean_var=False, axis=1, cudnn_off=False,
               _training=False):
    """Reference: src/operator/nn/batch_norm.cc. Returns
    (out, mean, invstd, new_moving_mean, new_moving_var): outputs 1-2 are
    the statistics the normalization used (batch moments in training,
    moving stats otherwise), surfaced to the user under
    output_mean_var=True — the second of them is the INVERSE standard
    deviation 1/sqrt(var+eps), matching the reference kernel's saved
    output ("outputs both data_mean and the inverse of data_var",
    batch_norm.cc); the runtime writes outputs 3-4 back into the aux
    arrays (MXNet mutates aux_states in the kernel).
    """
    axis = axis % data.ndim
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    shape = [1] * data.ndim
    shape[axis] = data.shape[axis]
    shape = tuple(shape)
    # statistics and the affine math run in f32 even for bf16 activations
    # (reference cuDNN BN accumulates in fp32 for fp16 inputs); the output
    # drops back to the input dtype so a bf16 chain stays bf16 end to end
    x32 = data.astype(jnp.float32)
    if _training and not use_global_stats:
        mean, var = _bn_stats(x32, axis)
        # the running-stat blend ALSO computes in f32 (f32 casts are
        # no-ops for the standard f32 aux store; a reduced-precision
        # store would otherwise round the momentum product per batch —
        # the convert/drift half of the BN-stat traffic). The updated
        # stats live in the donated aux store, so the whole update stays
        # inside the one fused step program.
        new_mm = (moving_mean.astype(jnp.float32) * momentum
                  + mean * (1 - momentum)).astype(moving_mean.dtype)
        new_mv = (moving_var.astype(jnp.float32) * momentum
                  + var * (1 - momentum)).astype(moving_var.dtype)
    else:
        mean, var = moving_mean, moving_var
        new_mm, new_mv = moving_mean, moving_var
    inv = lax.rsqrt(var.astype(jnp.float32) + eps)
    out = (x32 - mean.astype(jnp.float32).reshape(shape)) \
        * inv.reshape(shape) * g.astype(jnp.float32).reshape(shape) \
        + beta.astype(jnp.float32).reshape(shape)
    return (out.astype(data.dtype), jnp.asarray(mean), inv,
            new_mm, new_mv)


@register("LayerNorm")
def layer_norm(data, gamma, beta, axis=-1, eps=1e-5, output_mean_var=False):
    x32 = data.astype(jnp.float32)
    mean = jnp.mean(x32, axis=axis, keepdims=True)
    var = jnp.var(x32, axis=axis, keepdims=True)
    out = (x32 - mean) * lax.rsqrt(var + eps)
    shape = [1] * data.ndim
    shape[axis % data.ndim] = data.shape[axis % data.ndim]
    out = out * gamma.astype(jnp.float32).reshape(shape) \
        + beta.astype(jnp.float32).reshape(shape)
    return out.astype(data.dtype)


@register("RMSNorm")
def rms_norm(data, gamma, axis=-1, eps=1e-5):
    """``data / sqrt(mean(data^2) + eps) * gamma`` over ``axis``, the mean
    and the scaling in float32 (Zhang & Sennrich): LayerNorm without the
    mean and without a shift."""
    x32 = data.astype(jnp.float32)
    out = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=axis, keepdims=True) + eps)
    shape = [1] * data.ndim
    shape[axis % data.ndim] = data.shape[axis % data.ndim]
    return (out * gamma.astype(jnp.float32).reshape(shape)).astype(data.dtype)


@register("InstanceNorm")
def instance_norm(data, gamma, beta, eps=1e-3):
    red = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=red, keepdims=True)
    var = jnp.var(data, axis=red, keepdims=True)
    out = (data - mean) * lax.rsqrt(var + eps)
    shape = (1, -1) + (1,) * (data.ndim - 2)
    return out * gamma.reshape(shape) + beta.reshape(shape)


@register("L2Normalization")
def l2_normalization(data, eps=1e-10, mode="instance"):
    if mode == "instance":
        red = tuple(range(1, data.ndim))
        n = jnp.sqrt(jnp.sum(jnp.square(data), axis=red, keepdims=True) + eps)
    elif mode == "channel":
        n = jnp.sqrt(jnp.sum(jnp.square(data), axis=1, keepdims=True) + eps)
    else:  # spatial
        red = tuple(range(2, data.ndim))
        n = jnp.sqrt(jnp.sum(jnp.square(data), axis=red, keepdims=True) + eps)
    return data / n


@register("LRN")
def lrn(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5):
    """Local response norm across channels (src/operator/nn/lrn.cc)."""
    sq = jnp.square(data)
    half = nsize // 2
    padded = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    window = jnp.zeros_like(sq)
    for i in range(nsize):
        window = window + lax.dynamic_slice_in_dim(padded, i, data.shape[1], axis=1)
    return data * jnp.power(knorm + alpha * window / nsize, -beta)


# ---------------------------------------------------------------------------
# Dropout (stateful; reference src/operator/nn/dropout-inl.h)
# ---------------------------------------------------------------------------

@register("Dropout", stateful=True, needs_train_flag=True)
def dropout(data, p=0.5, mode="training", axes=(), _training=False):
    if p == 0.0 or (not _training and mode != "always"):
        return data
    shape = list(data.shape)
    for ax in axes:
        shape[ax] = 1
    keep = 1.0 - p
    mask = jax.random.bernoulli(next_rng_key(), keep, tuple(shape))
    return jnp.where(mask, data / keep, jnp.zeros_like(data))


# ---------------------------------------------------------------------------
# Embedding (reference src/operator/tensor/indexing_op.h EmbeddingOp)
# ---------------------------------------------------------------------------

@register("Embedding")
def embedding(data, weight, input_dim=0, output_dim=0, dtype="float32",
              sparse_grad=False):
    return jnp.take(weight, data.astype(jnp.int32), axis=0)


# ---------------------------------------------------------------------------
# UpSampling
# ---------------------------------------------------------------------------

@register("UpSampling")
def upsampling(*args, scale=1, sample_type="nearest", num_args=1,
               num_filter=0, multi_input_mode="concat", workspace=512):
    data = args[0]
    if sample_type == "nearest":
        outs = []
        for a in args:
            o = jnp.repeat(jnp.repeat(a, scale, axis=2), scale, axis=3)
            outs.append(o)
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
    # bilinear: weight is args[1]; use resize (deconv-equivalent capability)
    b, c, h, w = data.shape
    return jax.image.resize(data, (b, c, h * scale, w * scale), method="linear")


# ---------------------------------------------------------------------------
# Loss / output heads with MXNet's custom backward semantics.
# ---------------------------------------------------------------------------

def _softmax_output_impl(data, label, grad_scale, ignore_label, multi_output,
                         use_ignore, preserve_shape, normalization,
                         out_grad, smooth_alpha):
    if multi_output:
        out = jax.nn.softmax(data, axis=1)
    elif preserve_shape:
        out = jax.nn.softmax(data, axis=-1)
    else:
        out = jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)
    return out


@functools.lru_cache(maxsize=None)
def _make_softmax_output(grad_scale, ignore_label, multi_output, use_ignore,
                         preserve_shape, normalization, out_grad, smooth_alpha):
    @jax.custom_vjp
    def f(data, label):
        return _softmax_output_impl(data, label, grad_scale, ignore_label,
                                    multi_output, use_ignore, preserve_shape,
                                    normalization, out_grad, smooth_alpha)

    def fwd(data, label):
        out = f(data, label)
        return out, (out, label)

    def bwd(res, g):
        out, label = res
        if multi_output:
            # data: (B, C, ...); label: (B, ...)
            C = out.shape[1]
            lab = label.astype(jnp.int32)
            onehot = jax.nn.one_hot(lab, C, dtype=out.dtype)
            onehot = jnp.moveaxis(onehot, -1, 1)
            grad = out - onehot
            if smooth_alpha:
                grad = grad + smooth_alpha * (onehot - 1.0 / C)
            if use_ignore:
                mask = (label != ignore_label).astype(out.dtype)
                grad = grad * jnp.expand_dims(mask, 1)
            valid = (label != ignore_label).sum() if use_ignore else label.size
        else:
            C = out.shape[-1]
            flat = out.reshape(out.shape[0], -1)
            lab = label.reshape(-1).astype(jnp.int32)
            onehot = jax.nn.one_hot(lab, flat.shape[-1], dtype=out.dtype)
            grad = (flat - onehot).reshape(out.shape)
            if smooth_alpha:
                grad = grad + smooth_alpha * (onehot.reshape(out.shape) - 1.0 / C)
            if use_ignore:
                mask = (label != ignore_label).astype(out.dtype).reshape(
                    (-1,) + (1,) * (out.ndim - 1))
                grad = grad * mask
            valid = (label != ignore_label).sum() if use_ignore else label.shape[0]
        if normalization == "valid":
            grad = grad / jnp.maximum(valid, 1).astype(out.dtype)
        elif normalization == "batch":
            grad = grad / out.shape[0]
        grad = grad * grad_scale
        if out_grad:
            grad = grad * g
        return grad.astype(out.dtype), jnp.zeros_like(label)

    f.defvjp(fwd, bwd)
    return f


@register("SoftmaxOutput", aliases=("Softmax",), needs_train_flag=False)
def softmax_output(data, label, grad_scale=1.0, ignore_label=-1.0,
                   multi_output=False, use_ignore=False, preserve_shape=False,
                   normalization="null", out_grad=False, smooth_alpha=0.0):
    """Softmax forward; backward is d(CE)/d(data) directly, ignoring the head
    gradient — exactly the reference's semantics
    (src/operator/softmax_output-inl.h)."""
    f = _make_softmax_output(float(grad_scale), float(ignore_label),
                             bool(multi_output), bool(use_ignore),
                             bool(preserve_shape), str(normalization),
                             bool(out_grad), float(smooth_alpha))
    return f(data, label)


def _regression(name, fwd_fn, grad_fn):
    @functools.lru_cache(maxsize=None)
    def make(grad_scale):
        @jax.custom_vjp
        def f(data, label):
            return fwd_fn(data)

        def fwd(data, label):
            out = f(data, label)
            return out, (out, label)

        def bwd(res, g):
            out, label = res
            num = 1
            for s in out.shape[1:]:
                num *= s
            grad = grad_fn(out, label.reshape(out.shape)) * grad_scale / num
            return grad.astype(out.dtype), jnp.zeros_like(label)

        f.defvjp(fwd, bwd)
        return f

    @register(name)
    def op(data, label, grad_scale=1.0):
        return make(float(grad_scale))(data, label)
    op.__name__ = name
    return op


_regression("LinearRegressionOutput", lambda d: d, lambda o, l: o - l)
_regression("MAERegressionOutput", lambda d: d, lambda o, l: jnp.sign(o - l))
_regression("LogisticRegressionOutput", jax.nn.sigmoid, lambda o, l: o - l)


@register("MakeLoss", aliases=("make_loss",))
def make_loss(data, grad_scale=1.0, valid_thresh=0.0, normalization="null"):
    @jax.custom_vjp
    def f(x):
        return x

    def fwd(x):
        return x, x.shape

    def bwd(shape, g):
        grad = jnp.full(shape, grad_scale, dtype=g.dtype)
        if normalization == "batch":
            grad = grad / shape[0]
        return (grad,)

    f.defvjp(fwd, bwd)
    return f(data)


@register("BlockGrad", aliases=("stop_gradient",))
def block_grad(data):
    return lax.stop_gradient(data)


@register("identity", aliases=("_copy", "copy"))
def identity(data):
    return data


# ---------------------------------------------------------------------------
# KV-cached causal self-attention (the serving decode primitive, ISSUE 17).
# One op serves BOTH phases of autoregressive generation and training:
#   * prefill / training: pos=0, a T-token chunk writes cache rows 0..T-1
#     and each position t attends rows j <= t (exact causal attention —
#     feeding zero caches with pos=0 and S >= T degenerates to plain
#     causal self-attention, so the train and generate symbols share it);
#   * decode: T=1, pos=p writes row p and attends rows j <= p.
# The updated caches are real outputs: the serving engine compiles them
# as DONATED inputs aliased to outputs, so the packed per-slot KV state
# never leaves the device between steps.
# Correctness under padded prefill: rows past the true prompt length hold
# garbage K/V, but the causal mask only ever exposes row j once j <= pos
# of a later step — and the decode step at position j OVERWRITES row j
# before attending it, so garbage is never visible.
#
# Long-context route (ISSUE 20): under a seq_parallel() scope (or an
# ambient MeshContext) with a ``seq`` mesh axis, the FULL-WINDOW case
# (T == S, the pos=0 training/prefill configuration where the chunk
# covers the whole cache) computes the attention itself through
# parallel/ring_attention.py — each device holds T/n query rows and the
# K/V blocks rotate via ppermute, O(T/n) attention memory per device —
# while the cache writes stay as-is so the op contract is unchanged.
# Decode (T=1) and bucketed serving prefill (T < S) never route.
# ---------------------------------------------------------------------------

_SEQ_PARALLEL = []


class seq_parallel:
    """Scope routing full-window ``cached_attention`` through ring
    attention over ``mesh``'s ``seq`` axis. Enter it around the code
    that TRACES the program (``Module.fit``, an engine ``warm()``):
    the route is decided at trace time, costs nothing per step, and
    only engages when T == S and the seq axis divides T."""

    def __init__(self, mesh):
        self.mesh = mesh

    def __enter__(self):
        _SEQ_PARALLEL.append(self.mesh)
        return self.mesh

    def __exit__(self, *a):
        _SEQ_PARALLEL.pop()


def _seq_parallel_mesh(T, S, H):
    """The mesh to ring-route this cached_attention call over, or
    None for the dense path (no scope/ambient mesh, no ``seq`` axis,
    not the full-window configuration, or T not divisible)."""
    mesh = _SEQ_PARALLEL[-1] if _SEQ_PARALLEL else None
    if mesh is None:
        from ..parallel.mesh import current_mesh
        mesh = current_mesh()
    if mesh is None:
        return None
    from ..parallel.mesh import AXIS_SEQ
    n = mesh.axis_size(AXIS_SEQ)
    if n <= 1 or T != S or T % n:
        return None
    return mesh


@register("cached_attention", num_outputs=3)
def cached_attention(query, key, value, k_cache, v_cache, pos, valid_len=None,
                     q_gain=None, k_gain=None, num_heads=1, alibi=False,
                     num_kv_heads=0, window=0, rope_theta=0.0, norm_eps=1e-5):
    """query/key/value ``[B, T, D]``; caches ``[B, S, D]``; ``pos [B]``
    (write offset per sample). Returns ``(out, k_cache_next,
    v_cache_next)``.

    With every argument after ``alibi`` left at its default this is the
    multi-head op as it always was. ``num_kv_heads`` (grouped queries),
    ``window`` (a ring cache), ``rope_theta`` (rotary positions), the
    per-head gains ``q_gain``/``k_gain`` and ``valid_len`` (a padded
    chunk's true length) take :func:`_cached_attention_grouped`, which
    says what each means. ``alibi=True`` adds the parameter-free linear
    distance bias (Press et al.) — per-head slope ``2^(-8(i+1)/H)``
    times the query-key distance ``(pos + t) - s``. Because the
    distance is computed from the ABSOLUTE cache positions, the bias is
    bit-identical between a T-token prefill/training chunk and a
    one-token decode step — positional information with zero extra
    state to carry between steps.

    Inside a :class:`seq_parallel` scope the full-window case (T == S;
    callers feed pos=0 there — the training configuration) attends via
    ring attention over the mesh ``seq`` axis instead of the dense
    [T, S] score matrix; the cache outputs are unchanged."""
    p = pos.astype(jnp.int32).reshape(-1)
    B, T, D = query.shape
    S = k_cache.shape[1]
    H = int(num_heads)
    hd = D // H
    use_alibi = bool(alibi) and str(alibi).lower() not in ("false", "0")
    spec = _AttnSpec(H, int(num_kv_heads) or H, int(window),
                     float(rope_theta), float(norm_eps))
    if (spec.kv_heads != H or spec.window or spec.rope_theta
            or valid_len is not None or q_gain is not None):
        if use_alibi:
            raise ValueError("cached_attention: alibi goes with the "
                             "multi-head op only (no caller has both)")
        return _cached_attention_grouped(query, key, value, k_cache, v_cache,
                                         p, valid_len, q_gain, k_gain, spec)
    k_rows, v_rows = key.astype(k_cache.dtype), value.astype(v_cache.dtype)
    if _decode_path(query, k_cache, H):
        return _decode_step(query, k_rows, v_rows, k_cache, v_cache, p, spec,
                            use_alibi, with_grad=True)
    new_k = _scatter_rows(k_cache, k_rows, p)
    new_v = _scatter_rows(v_cache, v_rows, p)
    mesh = _seq_parallel_mesh(T, S, H)
    if mesh is not None:
        from ..parallel.ring_attention import ring_attention_sharded

        def heads_first(a):      # [B, T, D] -> [B, H, T, hd]
            return a.astype(query.dtype).reshape(
                B, T, H, hd).transpose(0, 2, 1, 3)

        o = ring_attention_sharded(
            heads_first(query), heads_first(key), heads_first(value),
            mesh, causal=True, data_axis=None, alibi=use_alibi)
        out = o.transpose(0, 2, 1, 3).reshape(B, T, D)
        return out.astype(query.dtype), new_k, new_v
    return (_attend_dense(query, new_k, new_v, p, H, use_alibi),
            new_k, new_v)


def _scatter_rows(cache, rows, at):
    """``cache [B, S, D]`` with ``rows [B, T, D]`` written from row
    ``at[b]`` of slot ``b`` on (the start clamped so the chunk fits): XLA's
    scatter, a ``while`` of a trip a slot on the TPU."""
    return jax.vmap(lambda c, r, a: lax.dynamic_update_slice(c, r, (a, 0)))(
        cache, rows, at)


def _attend_dense(query, new_k, new_v, p, H, use_alibi):
    """The dense formula: every query row against the whole cache,
    scores ``[B, H, T, S]`` in the query's dtype, masked to ``s <= pos +
    t``."""
    B, T, D = query.shape
    S = new_k.shape[1]
    hd = D // H
    qh = query.reshape(B, T, H, hd)
    kh = new_k.astype(query.dtype).reshape(B, S, H, hd)
    vh = new_v.astype(query.dtype).reshape(B, S, H, hd)
    scores = jnp.einsum("bthd,bshd->bhts", qh, kh) / jnp.sqrt(
        jnp.asarray(hd, query.dtype))
    t_idx = jnp.arange(T, dtype=jnp.int32)[None, :, None]
    s_idx = jnp.arange(S, dtype=jnp.int32)[None, None, :]
    q_abs = p[:, None, None] + t_idx                     # [B, T, 1]
    allowed = s_idx <= q_abs                             # [B, T, S]
    if use_alibi:
        slopes = jnp.asarray(
            [2.0 ** (-8.0 * (i + 1) / H) for i in range(H)],
            scores.dtype)
        dist = (q_abs - s_idx).astype(scores.dtype)      # [B, T, S]
        scores = scores - slopes[None, :, None, None] * dist[:, None]
    scores = jnp.where(allowed[:, None, :, :], scores,
                       jnp.asarray(-1e30, scores.dtype))
    att = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", att, vh).reshape(B, T, D)
    return out.astype(query.dtype)


# ---------------------------------------------------------------------------
# Grouped queries, ring caches, rotary positions, per-head norms: what a
# decoder with 64 query heads over 8 key/value heads, window layers among
# full ones and normalised queries and keys asks of the same op.
# ---------------------------------------------------------------------------

_AttnSpec = collections.namedtuple(
    "_AttnSpec", "heads kv_heads window rope_theta norm_eps")

_WINDOW_NODES = _obs.counter(
    "ops.cached_attention.window_nodes",
    "cached_attention nodes traced with a window (their cache is a ring)")


def _rms_head(x, gain, eps):
    """``x [..., hd]`` normalised over the head, times ``gain [hd]``."""
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return y * gain.astype(jnp.float32)


def _rope(x, positions, theta):
    """Rotary positions over the whole head in half-split pairs:
    ``x [B, T, H, hd]`` (float32), ``positions [B, T]`` absolute."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, :, None, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _ring_write(cache, rows, p, n):
    """A chunk into a ring: row ``r`` of the ring takes the chunk's newest
    TRUE row whose position is ``r`` modulo the ring's length, and keeps
    what it held where the chunk has none. ``rows [B, T, D]`` at positions
    ``p .. p + n - 1``; rows past ``n`` are padding and are never written."""
    B, T, _D = rows.shape
    R = cache.shape[1]
    if T == 1:                                 # a decode step: one true row
        return _scatter_rows(cache, rows, p % R)
    last = (p + n - 1)[:, None]                                  # [B, 1]
    r_idx = jnp.arange(R, dtype=jnp.int32)[None, :]
    at = last - (last - r_idx) % R             # newest position = r mod R
    fresh = (at >= p[:, None]) & (n[:, None] > 0)
    take = jnp.clip(at - p[:, None], 0, T - 1)
    picked = jnp.take_along_axis(rows, take[:, :, None], axis=1)
    return jnp.where(fresh[:, :, None], picked, cache)


def _cached_attention_grouped(query, key, value, k_cache, v_cache, p,
                              valid_len, q_gain, k_gain, spec):
    """The op for grouped queries over caches of two kinds.

    ``query [B, T, heads x hd]``, ``key``/``value [B, T, kv_heads x hd]``,
    caches ``[B, S, kv_heads x hd]``; query head ``i`` attends key/value
    head ``i // (heads / kv_heads)``. ``q_gain``/``k_gain [hd]``: queries
    and keys are RMS-normalised over each head first. ``rope_theta``:
    rotary positions at the absolute position ``pos + t``. Keys go into the
    cache AFTER norm and rotation, so a decode step reads them as they lie.

    ``window = 0``: the cache holds position ``s`` in row ``s`` and a query
    attends every ``s <= pos + t``. ``window > 0``: the cache is a RING of
    ``S >= window`` rows, position ``s`` in row ``s mod S``, and a query
    attends ``0 <= pos + t - s < window``. A chunk (``T > 1``) attends the
    ring as it was plus its own rows under that band, and leaves its last
    ``S`` TRUE rows in the ring: ``valid_len [B]`` says how many of the
    ``T`` rows are true (all, when absent), so padding never overwrites a
    live row. Scores and softmax are float32. One row a sample on heads of
    whole 128-lane slabs takes the kernel ``decode_attention``."""
    B, T, _Dq = query.shape
    S = k_cache.shape[1]
    H, K = spec.heads, spec.kv_heads
    hd = query.shape[2] // H
    n = (jnp.full((B,), T, jnp.int32) if valid_len is None
         else valid_len.astype(jnp.int32).reshape(-1))
    qh = query.reshape(B, T, H, hd).astype(jnp.float32)
    kh = key.reshape(B, T, K, hd).astype(jnp.float32)
    if q_gain is not None:
        qh = _rms_head(qh, q_gain, spec.norm_eps)
        kh = _rms_head(kh, k_gain, spec.norm_eps)
    t_idx = jnp.arange(T, dtype=jnp.int32)[None, :]
    q_abs = p[:, None] + t_idx                                    # [B, T]
    if spec.rope_theta:
        qh = _rope(qh, q_abs, spec.rope_theta)
        kh = _rope(kh, q_abs, spec.rope_theta)
    q = qh.astype(query.dtype).reshape(B, T, H * hd)
    k_rows = kh.astype(k_cache.dtype).reshape(B, T, K * hd)
    v_rows = value.astype(v_cache.dtype)
    if spec.window:
        _WINDOW_NODES.inc()
    if _decode_path(q, k_cache, H, K):
        return _decode_step(q, k_rows, v_rows, k_cache, v_cache, p, spec)
    if spec.window:
        new_k = _ring_write(k_cache, k_rows, p, n)
        new_v = _ring_write(v_cache, v_rows, p, n)
        # the ring as it was (row r holds the newest position below pos
        # that is r modulo S), then the chunk's own rows
        r_idx = jnp.arange(S, dtype=jnp.int32)[None, :]
        prev = (p - 1)[:, None]
        old_abs = prev - (prev - r_idx) % S
        k_abs = jnp.concatenate([old_abs, q_abs], axis=1)         # [B, S+T]
        live = jnp.concatenate(
            [old_abs >= 0, t_idx < n[:, None]], axis=1)
        out = _attend_grouped(
            q, jnp.concatenate([k_cache, k_rows], axis=1),
            jnp.concatenate([v_cache, v_rows], axis=1),
            q_abs, k_abs, live, spec)
    else:
        new_k = _scatter_rows(k_cache, k_rows, p)
        new_v = _scatter_rows(v_cache, v_rows, p)
        s_idx = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        out = _attend_grouped(q, new_k, new_v, q_abs, s_idx,
                              jnp.ones((B, S), bool), spec)
    return out, new_k, new_v


def _attend_grouped(q, keys, values, q_abs, k_abs, live, spec):
    """Every query row against ``keys [B, N, kv_heads x hd]`` that lie at
    the absolute positions ``k_abs [B, N]`` (``live`` where a row holds
    one): causal, banded by ``spec.window``, float32 scores and softmax."""
    B, T, _ = q.shape
    N = keys.shape[1]
    H, K = spec.heads, spec.kv_heads
    hd = q.shape[2] // H
    qg = q.reshape(B, T, K, H // K, hd)
    kg = keys.astype(q.dtype).reshape(B, N, K, hd)
    vg = values.astype(q.dtype).reshape(B, N, K, hd)
    scores = jnp.einsum("btkgd,bnkd->bkgtn", qg, kg,
                        preferred_element_type=jnp.float32) * hd ** -0.5
    dist = q_abs[:, :, None] - k_abs[:, None, :]                  # [B, T, N]
    allowed = (dist >= 0) & live[:, None, :]
    if spec.window:
        allowed &= dist < spec.window
    scores = jnp.where(allowed[:, None, None], scores, -1e30)
    att = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgtn,bnkd->btkgd", att, vg,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, T, H * hd).astype(q.dtype)


# One query row a sample (the decode step) runs on two Pallas kernels, in
# both halves of the op (:func:`_decode_step`). ``cache_write_row`` puts
# the step's key and value row into the caches in place, moving the one
# 16-row block that holds each slot's row; XLA's scatter of a row a slot
# is a ``while`` of a trip a slot (3.3 ms of BLOOM-1b7's 9.3 ms decode
# program on the v5e, PERF.md PR 33). ``decode_attention`` then reads K and
# V in the [B, S, D] tiling they are stored in and only the blocks at or
# below pos[b]; the dense formula re-tiles both whole caches to heads-minor
# every step (19.6 ms of 36.9 ms, PERF.md PR 28) and attends all S rows.
# Which path a call takes is read off its shapes at trace time and nothing
# else: T == 1, a head of whole 128-lane slabs (so a head is a column
# block of the stored tile), a block that divides S, and no ambient mesh
# (a kernel is one device's program; under a mesh GSPMD partitions the
# dense formula). A cache that is not whole 16-row blocks keeps the
# scatter in front of the attention kernel. Prefill, training and every
# small-head model take the scatter and the dense formula as before.
_DECODE_PATH_NODES = _obs.counter(
    "ops.cached_attention.decode_path",
    "cached_attention nodes traced onto the one-token decode kernel")
_ROW_WRITE_NODES = _obs.counter(
    "ops.cached_attention.row_write",
    "cached_attention nodes traced with their cache rows written by the "
    "kernel cache_write_row")


def decode_path_nodes():
    """How many ``cached_attention`` nodes this process has traced onto
    the decode kernel so far (``InferenceEngine.stats()`` reports the
    count per generate program)."""
    return _DECODE_PATH_NODES.default().value


def row_write_nodes():
    """How many of them wrote their rows through ``cache_write_row``."""
    return _ROW_WRITE_NODES.default().value


def _decode_path(query, cache, H, kv_heads=None):
    """Whether this call's shapes put it on the decode kernel."""
    B, T, D = query.shape
    if T != 1 or D % H or (D // H) % 128:
        return False
    if kv_heads not in (None, H) and (H % kv_heads or H % 8):
        return False        # a group's query heads are the kernel's rows
    from ..parallel.mesh import current_mesh
    if _SEQ_PARALLEL or current_mesh() is not None:
        return False
    from .pallas_attention import decode_block
    return decode_block(cache.shape[1], cache.shape[2],
                        cache.dtype) is not None


def _decode_step(q, k_rows, v_rows, k_cache, v_cache, p, spec,
                 use_alibi=False, with_grad=False):
    """A decode step whose shapes :func:`_decode_path` admits, for both
    halves of the op: the step's rows go into both caches, through the
    row-write kernel where they are whole blocks, and ``decode_attention``
    attends them. ``with_grad``: under the ``custom_vjp`` that gives the
    multi-head formula's gradient; nothing differentiates a grouped
    step."""
    from .pallas_attention import WRITE_ROWS
    by_kernel = k_cache.shape[1] % WRITE_ROWS == 0
    _DECODE_PATH_NODES.inc()
    if by_kernel:
        _ROW_WRITE_NODES.inc()
    step = _decode_kernels_with_grad if with_grad else _decode_kernels
    return step(q, k_rows, v_rows, k_cache, v_cache, p, spec, use_alibi,
                by_kernel)


def _decode_kernels(q, k_rows, v_rows, k_cache, v_cache, p, spec, use_alibi,
                    by_kernel):
    from .pallas_attention import cache_write_row, decode_attention
    S = k_cache.shape[1]
    # row pos[b], modulo a ring's length; an idle slot may count past a
    # cache: held inside it, as dynamic_update_slice holds its start
    at = p % S if spec.window else jnp.clip(p, 0, S - 1)
    write = cache_write_row if by_kernel else _scatter_rows
    new_k, new_v = write(k_cache, k_rows, at), write(v_cache, v_rows, at)
    out = decode_attention(q, new_k, new_v, p, spec.heads, alibi=use_alibi,
                           num_kv_heads=spec.kv_heads, window=spec.window)
    return out, new_k, new_v


_decode_kernels_with_grad = jax.custom_vjp(_decode_kernels,
                                           nondiff_argnums=(6, 7, 8))


def _decode_kernels_fwd(*args):
    return _decode_kernels(*args), args[:6]


def _decode_kernels_bwd(spec, use_alibi, by_kernel, res, g):
    # the kernels are forward only: differentiate the formula they compute
    *diff, p = res

    def formula(q, k_rows, v_rows, k_cache, v_cache):
        new_k = _scatter_rows(k_cache, k_rows, p)
        new_v = _scatter_rows(v_cache, v_rows, p)
        return (_attend_dense(q, new_k, new_v, p, spec.heads, use_alibi),
                new_k, new_v)

    _, vjp = jax.vjp(formula, *diff)
    return vjp(g) + (None,)


_decode_kernels_with_grad.defvjp(_decode_kernels_fwd, _decode_kernels_bwd)


# ---------------------------------------------------------------------------
# Latent attention (DeepSeek-V2/V3's multi-head latent attention): a
# position is ONE cached row ``[c ; k_rope]`` shared by all heads. Keys and
# values are expanded from it a head (a chunk), or the expansion is absorbed
# into the query and the output (one row a sample: the decode step, on the
# kernel ``latent_decode_attention``). Neither half of ``cached_attention``
# can express it: both take a key cache and a value cache of ``kv_heads x
# head_dim`` columns, and the value here is the first ``rank`` columns of the
# key's row.
# ---------------------------------------------------------------------------

_LatentSpec = collections.namedtuple(
    "_LatentSpec", "heads nope rope v_dim rank scale freqs norm_eps")

_LATENT_DECODE_NODES = _obs.counter(
    "ops.latent_attention.decode_path",
    "latent_attention nodes traced onto the kernel latent_decode_attention")


def latent_decode_nodes():
    """How many ``latent_attention`` nodes this process has traced onto the
    latent decode kernel so far."""
    return _LATENT_DECODE_NODES.default().value


_LATENT_BLOCKWISE_NODES = _obs.counter(
    "ops.latent_attention.chunk_blockwise",
    "latent_attention nodes traced onto the block-wise chunk path (the "
    "kernel latent_prefill_attention)")


def latent_blockwise_nodes():
    """How many ``latent_attention`` nodes this process has traced onto the
    block-wise chunk path so far."""
    return _LATENT_BLOCKWISE_NODES.default().value


def yarn_frequencies(rope_dim, theta, factor=1.0, beta_fast=32.0,
                     beta_slow=1.0, orig_len=4096):
    """The rotary pairs' angular frequencies under YaRN, ``[rope_dim / 2]``
    floats: ``f_j = theta^(-2j / rope_dim)`` blended toward ``f_j / factor``
    by a ramp over the pairs that turn between ``beta_fast`` and
    ``beta_slow`` times in ``orig_len`` positions (``factor`` 1: plain
    rotary positions)."""
    import math
    half = int(rope_dim) // 2

    def pair_turning(turns):
        return (rope_dim * math.log(orig_len / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_turning(beta_fast)), 0)
    high = min(math.ceil(pair_turning(beta_slow)), half - 1)
    out = []
    for j in range(half):
        f = theta ** (-2.0 * j / rope_dim)
        g = min(max((j - low) / max(high - low, 1e-3), 0.0), 1.0)
        out.append(f * ((1.0 - g) + g / factor))
    return tuple(out)


def _rotate(x, positions, freqs):
    """Half-split rotary pairs at the angular frequencies ``freqs``: ``x
    [B, T, ..., rope]`` (float32), ``positions [B, T]`` absolute."""
    half = x.shape[-1] // 2
    ang = (positions.astype(jnp.float32).reshape(
        positions.shape + (1,) * (x.ndim - 2))
        * jnp.asarray(freqs, jnp.float32))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _pad_columns(x, width):
    """``x [..., w]`` with zeros up to ``width`` columns."""
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


@register("latent_attention", num_outputs=2)
def latent_attention(query, kv_row, c_gain, kv_up_weight, cache, pos,
                     num_heads=1, nope_dim=128, rope_dim=64, v_dim=128,
                     scale=0.0, rope_theta=10000.0, rope_factor=1.0,
                     rope_beta_fast=32.0, rope_beta_slow=1.0,
                     rope_orig_len=4096, norm_eps=1e-6, pos_scale_beta=0.0):
    """Causal attention over a latent cache. Returns ``(out, cache_next)``.

    ``query [B, T, heads x (nope_dim + rope_dim)]``: head ``i``'s ``[q_nope
    ; q_rope]``. ``kv_row [B, T, rank + rope_dim]``: ``[c ; k_rope]`` as the
    down projection gives them; ``c`` is RMS-normalised here (gain ``c_gain
    [rank]``) and ``q_rope``, ``k_rope`` are rotated at the absolute
    position ``pos + t`` (YaRN frequencies from the ``rope_*`` attributes,
    half-split pairs). ``kv_up_weight [heads x (nope_dim + v_dim), rank]``:
    head ``i``'s ``[k_nope ; v] = W_i c``. ``cache [B, S, W]``, ``W >= rank
    + rope_dim``: row ``s`` holds position ``s`` as ``[c ; k_rope]`` after
    norm and rotation, in the cache's dtype, zeros in any columns past them
    (a row padded to whole 128-lane slabs keeps XLA from giving the cache a
    positions-minor layout on the TPU, which would cost a cache-sized copy
    in front of each kernel); ``pos [B]`` the write offset. ``score_i(t, s)
    = scale (q_nope,i . k_nope,i(s) + q_rope,i . k_rope(s))`` for ``s <= pos
    + t`` (``scale`` 0: ``(nope_dim + rope_dim)^-1/2``), float32 softmax,
    ``out [B, T, heads x v_dim]``. ``pos_scale_beta`` not 0: the query at
    absolute position ``p`` is first multiplied by ``g(p) = 1 +
    pos_scale_beta ln(1 + floor(p / rope_orig_len))`` (a score's scale that
    grows with the position by whole original contexts), in every path.

    A chunk expands keys and values. Where query, key and value heads are
    one width of whole 128-lane slabs (``_latent_blockwise_path``) it
    expands its OWN ``T`` rows once and attends them tile by tile on the
    kernel ``latent_prefill_attention``, then, block after block of the
    cache, the live rows in front of it (none at ``pos`` 0, a prefill), so
    its work follows ``pos + T`` and no ``[B, heads, T, S]`` scores exist;
    any other chunk keeps the dense formulas over the whole cache. One
    row a sample absorbs the expansion instead, ``q_lat,i = W_uk,i^T
    q_nope,i`` and ``o_i = W_uv,i sum_s att c_s``, writes its row through
    ``cache_write_row`` and attends on ``latent_decode_attention``: the same
    mathematics in another order (``_latent_decode_path`` says when)."""
    spec = _LatentSpec(
        int(num_heads), int(nope_dim), int(rope_dim), int(v_dim),
        kv_row.shape[2] - int(rope_dim),
        float(scale) or (int(nope_dim) + int(rope_dim)) ** -0.5,
        yarn_frequencies(int(rope_dim), float(rope_theta), float(rope_factor),
                         float(rope_beta_fast), float(rope_beta_slow),
                         float(rope_orig_len)),
        float(norm_eps))
    B, T, _ = query.shape
    H, rank = spec.heads, spec.rank
    p = pos.astype(jnp.int32).reshape(-1)
    q_abs = p[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]   # [B, T]
    q = query.reshape(B, T, H, spec.nope + spec.rope)
    q_nope = q[..., :spec.nope]
    q_rope = _rotate(q[..., spec.nope:].astype(jnp.float32), q_abs,
                     spec.freqs)
    beta, g = float(pos_scale_beta), None
    if beta:
        g = 1.0 + beta * jnp.log1p(jnp.floor(
            q_abs.astype(jnp.float32) / float(rope_orig_len)))    # [B, T]
        q_rope = q_rope * g[:, :, None, None]
    q_rope = q_rope.astype(query.dtype)
    row32 = kv_row.astype(jnp.float32)
    c = _rms_head(row32[..., :rank], c_gain, spec.norm_eps)
    k_rope = _rotate(row32[..., rank:], q_abs, spec.freqs)
    rows = _pad_columns(jnp.concatenate([c, k_rope], axis=-1),
                        cache.shape[2]).astype(cache.dtype)
    w_up = kv_up_weight.reshape(H, spec.nope + spec.v_dim, rank)
    if _latent_decode_path(query, cache, rank):
        return _latent_decode_step(q_nope, q_rope, rows, w_up, cache, p, spec,
                                   g)
    if g is not None:
        q_nope = (q_nope.astype(jnp.float32)
                  * g[:, :, None, None]).astype(query.dtype)
    if _latent_blockwise_path(query, cache, spec):
        return _latent_chunk_blockwise(q_nope, q_rope, rows, w_up, cache, p,
                                       spec)
    new_cache = _scatter_rows(cache, rows, p)
    seen = new_cache.astype(query.dtype)
    kv = jnp.einsum("bsr,hor->bsho", seen[..., :rank], w_up.astype(query.dtype),
                    preferred_element_type=jnp.float32).astype(query.dtype)
    scores = spec.scale * (
        jnp.einsum("bthd,bshd->bhts", q_nope, kv[..., :spec.nope],
                   preferred_element_type=jnp.float32)
        + jnp.einsum("bthd,bsd->bhts", q_rope,
                     seen[..., rank:rank + spec.rope],
                     preferred_element_type=jnp.float32))
    s_idx = jnp.arange(cache.shape[1], dtype=jnp.int32)[None, None, :]
    allowed = s_idx <= q_abs[:, :, None]                          # [B, T, S]
    scores = jnp.where(allowed[:, None], scores, -1e30)
    att = jax.nn.softmax(scores, axis=-1).astype(query.dtype)
    out = jnp.einsum("bhts,bshd->bthd", att, kv[..., spec.nope:],
                     preferred_element_type=jnp.float32)
    return out.reshape(B, T, H * spec.v_dim).astype(query.dtype), new_cache


def _ambient_mesh():
    """Whether a mesh or sequence parallelism is ambient: a kernel is one
    device's program, so the latent paths that run one stay off then."""
    from ..parallel.mesh import current_mesh
    return bool(_SEQ_PARALLEL) or current_mesh() is not None


def _latent_decode_path(query, cache, rank):
    """Whether this call's shapes put it on the latent decode kernel: one
    row a sample, a rank of whole 128-lane slabs, a block that divides the
    cache, and no ambient mesh (a kernel is one device's program). The
    published row need not fill the cache's columns: the kernel's query is
    padded with zeros to the cache's width (``rank + rope_dim`` values in
    640 columns, or 320 in 384)."""
    if query.shape[1] != 1 or rank % 128 or _ambient_mesh():
        return False
    from .pallas_attention import latent_block
    return latent_block(cache.shape[1], cache.shape[2]
                        * jnp.dtype(cache.dtype).itemsize) is not None


def _latent_blockwise_path(query, cache, spec):
    """Whether this chunk's shapes put it on the block-wise path: query and
    key heads (``nope + rope``) as wide as value heads, in whole 128-lane
    slabs (one tile shape serves all three); whole tiles of 8 query rows;
    a block of the cache that divides it; and no ambient mesh (a kernel is
    one device's program)."""
    from .pallas_attention import PREFILL_BLOCK_K, prefill_block
    width = spec.nope + spec.rope
    if (width != spec.v_dim or width % 128 or query.shape[1] % 8
            or _ambient_mesh()):
        return False
    return prefill_block(cache.shape[1], PREFILL_BLOCK_K) is not None


def _latent_chunk_blockwise(q_nope, q_rope, rows, w_up, cache, p, spec):
    """A chunk on the kernel ``latent_prefill_attention``: its own ``T``
    rows, expanded once into per-head keys ``[k_nope,i ; k_rope]`` and
    values, attended causally; then the cache's live rows in front of it, a
    block at a time for as many blocks as the furthest sample's ``pos``
    needs (none at ``pos`` 0), each block expanded, attended and merged by
    the rows' log-sum-exp. Work and memory follow ``pos + T``."""
    from .pallas_attention import (PREFILL_BLOCK_K, latent_prefill_attention,
                                   prefill_block)
    _LATENT_BLOCKWISE_NODES.inc()
    B, T, H, _ = q_nope.shape
    S = cache.shape[1]
    dtype, rank, d = q_nope.dtype, spec.rank, spec.v_dim
    w = w_up.astype(dtype)

    def expand(seen):
        """``seen [B, N, W]`` rows of the cache to keys and values ``[B, N,
        H x d]``, head ``i``'s ``[k_nope,i ; k_rope]`` and ``v_i``."""
        seen = seen.astype(dtype)
        kv = jnp.einsum("bsr,hor->bsho", seen[..., :rank], w,
                        preferred_element_type=jnp.float32).astype(dtype)
        k_rope = jnp.broadcast_to(
            seen[:, :, None, rank:rank + spec.rope],
            kv.shape[:3] + (spec.rope,))
        keys = jnp.concatenate([kv[..., :spec.nope], k_rope], axis=-1)
        return (keys.reshape(kv.shape[:2] + (H * d,)),
                kv[..., spec.nope:].reshape(kv.shape[:2] + (H * d,)))

    new_cache = _scatter_rows(cache, rows, p)
    q = jnp.concatenate([q_nope, q_rope], axis=-1).reshape(B, T, H * d)
    # the chunk's rows as the cache holds them (rounded to its dtype)
    out, lse = latent_prefill_attention(q, *expand(rows), 0, T, H,
                                        spec.scale)
    block = prefill_block(S, PREFILL_BLOCK_K)

    def in_front(carry):
        j, out, lse = carry
        seen = lax.dynamic_slice_in_dim(new_cache, j * block, block, axis=1)
        o2, lse2 = latent_prefill_attention(
            q, *expand(seen), S, jnp.clip(p - j * block, 0, block), H,
            spec.scale)
        both = jnp.logaddexp(lse, lse2)

        def weigh(o, l):    # [B, T, H x d] by [B, H, T, 1]
            share = jnp.exp(l - both)[..., 0].swapaxes(1, 2)
            return o.reshape(B, T, H, d) * share[..., None]
        merged = (weigh(out, lse) + weigh(o2.astype(jnp.float32), lse2))
        return j + 1, merged.reshape(B, T, H * d), both

    _, out, _ = lax.while_loop(
        lambda carry: carry[0] * block < jnp.max(p), in_front,
        (jnp.zeros((), jnp.int32), out.astype(jnp.float32), lse))
    return out.astype(dtype), new_cache


def _latent_decode_step(q_nope, q_rope, rows, w_up, cache, p, spec, g=None):
    """The absorbed form on two kernels: the step's row goes into the cache
    in place (``cache_write_row``: the attention kernel's blocks of whole
    16-row tiles are its blocks too), then ``latent_decode_attention`` reads
    each live row once for all heads. ``g [B, 1]``: the position's query
    scale, which multiplies the absorbed query in float32 (``q_rope``
    carries it already)."""
    from .pallas_attention import cache_write_row, latent_decode_attention
    B, S, W = cache.shape
    dtype = q_nope.dtype
    _LATENT_DECODE_NODES.inc()
    # an idle slot may count past the cache: held inside it
    new_cache = cache_write_row(cache, rows, jnp.clip(p, 0, S - 1))
    w_uk, w_uv = w_up[:, :spec.nope], w_up[:, spec.nope:]
    # heads lead as the batch dimension of both sides: the CPU backend has
    # no bfloat16 product with float32 out for "bhd,hdr->bhr"
    q_lat = jnp.einsum("hbd,hdr->hbr", q_nope[:, 0].swapaxes(0, 1),
                       w_uk.astype(dtype), preferred_element_type=jnp.float32
                       ).swapaxes(0, 1)
    if g is not None:
        q_lat = q_lat * g[:, :, None]
    q_lat = q_lat.astype(dtype)
    q_cat = _pad_columns(jnp.concatenate([q_lat, q_rope[:, 0]], -1), W)
    o_lat = latent_decode_attention(q_cat, new_cache, p, spec.rank,
                                    spec.scale)
    out = jnp.einsum("bhr,hvr->bhv", o_lat, w_uv.astype(dtype),
                     preferred_element_type=jnp.float32)
    return (out.reshape(B, 1, spec.heads * spec.v_dim).astype(dtype),
            new_cache)


# ---------------------------------------------------------------------------
# Hyper-connections (Zhu et al., arXiv 2409.19606) in their manifold-
# constrained form (mHC, arXiv 2512.24880): ``n`` residual streams, and
# around every sub-layer a read weight a stream, a write weight a stream and
# an ``n x n`` stream matrix that Sinkhorn iterations make doubly
# stochastic, all three functions of the token's streams. ``hyper_mix`` is
# what runs before the sub-layer, ``hyper_merge`` what runs after it.
# ---------------------------------------------------------------------------

_HYPER_MIX_NODES = _obs.counter(
    "ops.hyper_mix.nodes", "hyper_mix nodes traced (two a decoder layer)")


def hyper_mix_nodes():
    return _HYPER_MIX_NODES.default().value


def sinkhorn(logits, iters, eps):
    """``logits [..., n, n]`` (float32) to a matrix whose rows and columns
    sum to one: ``exp``, then ``iters`` times ``M / (rowsum + eps)`` and ``M
    / (colsum + eps)``, every one of them whatever a tolerance would
    forgive."""
    m = jnp.exp(logits)
    for _ in range(int(iters)):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


@register("hyper_mix", num_outputs=3)
def hyper_mix(streams, phi, alpha, base, sinkhorn_iters=20, eps=1e-6,
              clamp_min=-30.0, clamp_max=30.0):
    """``streams [B, T, n, d]``; ``phi [n d, n (n + 2)]``, ``alpha [3]``,
    ``base [n (n + 2)]`` in float32. Returns ``(read [B, T, d], carried [B,
    T, n, d], write [B, T, n])``:

    ``x~ = vec(X) rsqrt(mean(vec(X)^2) + eps)`` (no gain), ``m = x~ phi``;
    ``a = sigmoid(alpha_0 m[:n] + base[:n])``; ``write = 2 sigmoid(alpha_1
    m[n:2n] + base[n:2n])``; ``R = sinkhorn(clamp(alpha_2 mat(m[2n:]) +
    mat(base[2n:]), clamp_min, clamp_max))``; ``read = sum_j a_j X[j]`` (the
    sub-layer's input before its norm, in ``streams``' dtype); ``carried[i]
    = sum_j R[i, j] X[j]`` (float32). All of it in float32, the product with
    ``phi`` at the highest precision: the TPU's default would round both
    sides to bfloat16. The sums over streams are multiply-adds, not matrix
    products, for the same reason."""
    _HYPER_MIX_NODES.inc()
    B, T, n, d = streams.shape
    eps = float(eps)
    x = streams.astype(jnp.float32)
    flat = x.reshape(B, T, n * d)
    flat = flat * lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True) + eps)
    m = jnp.einsum("btk,kc->btc", flat, phi.astype(jnp.float32),
                   precision=lax.Precision.HIGHEST)
    alpha, base = alpha.astype(jnp.float32), base.astype(jnp.float32)
    a = jax.nn.sigmoid(alpha[0] * m[..., :n] + base[:n])
    write = 2.0 * jax.nn.sigmoid(alpha[1] * m[..., n:2 * n] + base[n:2 * n])
    logits = jnp.clip(alpha[2] * m[..., 2 * n:] + base[2 * n:],
                      float(clamp_min), float(clamp_max))
    R = sinkhorn(logits.reshape(B, T, n, n), sinkhorn_iters, eps)
    read = jnp.sum(a[..., None] * x, axis=2)
    carried = jnp.sum(R[..., None] * x[:, :, None], axis=3)
    return read.astype(streams.dtype), carried, write


@register("hyper_merge")
def hyper_merge(carried, write, data):
    """``carried [B, T, n, d]`` and ``write [B, T, n]`` (``hyper_mix``'s, in
    float32) with the sub-layer's output ``data [B, T, d]``: ``X'[i] =
    carried[i] + write_i y``, in ``data``'s dtype."""
    y = data.astype(jnp.float32)[:, :, None, :]
    return (carried + write[..., None] * y).astype(data.dtype)


# ---------------------------------------------------------------------------
# Mixture-of-Experts FFN (ISSUE 20): the symbol-level wrapper over
# parallel/moe.py's einsum dispatch/combine, so Module-built transformers
# can carry an expert layer. With the expert weights rule-sharded over the
# ``expert`` mesh axis (PartitionRules + Module.set_sharding), GSPMD lowers
# the ecd/ech dispatch einsums to the expert all-to-all automatically.
# ---------------------------------------------------------------------------

@register("moe_ffn", num_outputs=2)
def moe_ffn(data, gate_weight, w1, b1, w2, b2, capacity_factor=1.25,
            num_selected=1):
    """Expert feed-forward over the token dimension. ``data``
    ``[B, T, D]`` (or already-flat ``[T, D]``); ``gate_weight
    [D, E]``; ``w1 [E, D, H]``; ``b1 [E, H]``; ``w2 [E, H, D]``;
    ``b2 [E, D]``. Returns ``(y, aux)`` — y shaped like data, aux a
    ``(1,)`` Switch load-balancing loss (fraction * mean-prob per
    expert; wire it into the training head or drop it — the combine
    path keeps the gate differentiable either way)."""
    from ..parallel.moe import moe_ffn as _moe_ffn
    shape = data.shape
    x = data.reshape(-1, shape[-1])
    y, aux = _moe_ffn(x, gate_weight, w1, b1, w2, b2,
                      capacity_factor=float(capacity_factor),
                      num_selected=int(num_selected))
    return y.reshape(shape).astype(data.dtype), aux.reshape(1)


_MOE_ASSIGNMENTS = _obs.counter(
    "ops.moe_ffn.assignments",
    "token-to-expert assignments an expert layer routed, held here or not",
    ("layer",))
_MOE_ASSIGNMENTS_HELD = _obs.counter(
    "ops.moe_ffn.assignments_held",
    "assignments that fell on an expert this device holds", ("layer",))
_MOE_MAX_LOAD = _obs.gauge(
    "ops.moe_ffn.max_load",
    "the fullest held expert's assignments over the held experts' mean, "
    "between the last two readings of the device sums (1 = even)",
    ("layer",))
_MOE_EXPERTS_HIT = _obs.gauge(
    "ops.moe_ffn.experts_hit",
    "held experts with at least one assignment, a one-row (decode) run of "
    "the layer on average, between the last two readings", ("layer",))
_MOE_EXPERTS_HIT_RUN = _obs.gauge(
    "ops.moe_ffn.experts_hit_run",
    "held experts with at least one assignment, a run of the layer (decode "
    "step or chunk) on average, between the last two readings", ("layer",))

_MOE_WINDOW_NODES = _obs.counter(
    "ops.moe_ffn.held_window",
    "moe_ffn_held nodes traced with their held rows walked in windows of "
    "the sorted order (parallel/moe.py::held_window)")


def held_window_nodes():
    """How many ``moe_ffn_held`` nodes this process has traced onto the walk
    in windows so far."""
    return _MOE_WINDOW_NODES.default().value


# columns of ``moe_ffn_held``'s counts after the held experts' own
MOE_LOAD_EXTRA = 5      # all assignments; experts hit, runs (one row a
#                         sample); experts hit, runs (chunks)


def publish_moe_load(layer, delta):
    """Fold ``delta`` (``moe_ffn_held``'s ``load`` gained since the last
    call: each held expert's assignments, then the ``MOE_LOAD_EXTRA``
    columns) into the registry: the counters grow by it, the gauges say
    how that interval went. The serving engine calls it when it reads its
    device sums (``InferenceEngine.stats()`` has the whole per-expert
    table); nothing reads the device in a step."""
    held = [int(n) for n in delta[:-MOE_LOAD_EXTRA]]
    total, hit_step, steps, hit_chunk, chunks = (
        int(n) for n in delta[-MOE_LOAD_EXTRA:])
    _MOE_ASSIGNMENTS.labels(layer).inc(total)
    _MOE_ASSIGNMENTS_HELD.labels(layer).inc(sum(held))
    if sum(held):
        _MOE_MAX_LOAD.labels(layer).set(max(held) * len(held) / sum(held))
    if steps:
        _MOE_EXPERTS_HIT.labels(layer).set(hit_step / steps)
    if steps + chunks:
        _MOE_EXPERTS_HIT_RUN.labels(layer).set(
            (hit_step + hit_chunk) / (steps + chunks))


# what a generate state of kind ``sum:<name>`` feeds, by name
SUM_PUBLISHERS = {"moe_load": publish_moe_load}


@register("moe_ffn_held", num_outputs=2)
def moe_ffn_held(data, router_weight, select_bias, gate_weight, up_weight,
                 down_weight, load=None, valid_len=None, top_k=1,
                 expert_first=0, scale=1.0, scoring="sigmoid"):
    """Top-``top_k`` expert layer over ALL the router's experts, computed
    on the experts this device holds (``parallel/moe.py::moe_ffn_held``):
    ``data [B, T, D]`` (or ``[N, D]``); ``router_weight [E, D]``;
    ``select_bias [E]``; ``gate_weight, up_weight [held, D, F]``,
    ``down_weight [held, F, D]``, the experts ``expert_first ..``.
    ``scoring``: the router's scores are the ``"sigmoid"`` of its logits
    (the default) or their ``"softmax"`` over all ``E`` experts; either way
    the ``top_k`` largest of ``score + select_bias`` are chosen and their
    scores renormalised (``parallel/moe.py::route_topk``). Returns
    ``(y, load_next)``: ``y`` like ``data``, the held experts' weighted
    SiLU-gated outputs; ``load_next = load + counts`` with ``counts [1,
    held + MOE_LOAD_EXTRA]`` int32: the assignments of each held expert,
    all assignments, then the held experts that got any and 1, in the
    first pair of columns for a run of one row a sample (a decode step)
    and in the second for a chunk (``load`` absent: the counts; a sum
    wraps like any int32, its reader takes differences modulo 2^32). No
    capacity, no dropped token.
    ``valid_len [B]``: rows ``t >= valid_len[b]`` of a padded ``[B, T, D]``
    chunk are padding: they are neither computed nor counted, and come
    back zero."""
    from ..parallel.moe import held_window, moe_ffn_held as _held
    shape = data.shape
    if held_window(data.size // shape[-1] * int(top_k), gate_weight.shape[0],
                   router_weight.shape[0]) is not None:
        _MOE_WINDOW_NODES.default().inc()
    rows = None
    if valid_len is not None and data.ndim == 3:
        rows = (jnp.arange(shape[1], dtype=jnp.int32)[None, :]
                < valid_len.astype(jnp.int32).reshape(-1, 1)).reshape(-1)
    y, counts = _held(data.reshape(-1, shape[-1]), router_weight,
                      select_bias, gate_weight, up_weight, down_weight,
                      int(top_k), int(expert_first), float(scale), rows,
                      str(scoring))
    one_row = data.ndim == 3 and shape[1] == 1
    run = jnp.stack([jnp.sum(counts[:-1] > 0, dtype=counts.dtype),
                     jnp.ones((), counts.dtype)])
    counts = jnp.concatenate([counts, run * int(one_row),
                              run * int(not one_row)])[None]
    if load is not None:
        counts = load + counts.astype(load.dtype)
    return y.reshape(shape), counts


@register("SVMOutput")
def svm_output(data, label, margin=1.0, regularization_coefficient=1.0,
               use_linear=False):
    @jax.custom_vjp
    def f(d, l):
        return d

    def fwd(d, l):
        return d, (d, l)

    def bwd(res, g):
        d, l = res
        lab = l.astype(jnp.int32)
        onehot = jax.nn.one_hot(lab, d.shape[1], dtype=d.dtype)
        score_t = jnp.sum(d * onehot, axis=1, keepdims=True)
        viol = (d - score_t + margin) > 0
        if use_linear:
            grad = jnp.where(viol, regularization_coefficient, 0.0)
        else:
            grad = jnp.where(viol, 2 * regularization_coefficient *
                             (d - score_t + margin), 0.0)
        grad = grad * (1 - onehot) - onehot * jnp.sum(grad * (1 - onehot),
                                                      axis=1, keepdims=True)
        return grad.astype(d.dtype), jnp.zeros_like(l)

    f.defvjp(fwd, bwd)
    return f(data, label)


# ---------------------------------------------------------------------------
# CTC loss (reference: src/operator/contrib/ctc_loss.cc, vendored warp-ctc).
# TPU-native design: log-space forward DP expressed as one lax.scan over
# time — a single compiled kernel, batch-vectorised over (N, S), instead of
# warp-ctc's per-sample CUDA workspace machinery.
# ---------------------------------------------------------------------------

@register("ctc_loss", aliases=("CTCLoss", "_contrib_ctc_loss",
                               "_contrib_CTCLoss"))
def ctc_loss(data, label, data_lengths=None, label_lengths=None,
             use_data_lengths=False, use_label_lengths=False,
             blank_label="first"):
    """CTC negative log-likelihood.

    data: (T, N, C) unnormalised activations (softmax applied internally,
    matching the reference); label: (N, L) int labels padded with 0 (when
    blank is 'first') or -1; returns per-sample loss of shape (N,).
    """
    T, N, C = data.shape
    L = label.shape[1]
    S = 2 * L + 1
    logp = jax.nn.log_softmax(data.astype(jnp.float32), axis=2)
    lab = label.astype(jnp.int32)
    blank = 0 if blank_label == "first" else C - 1
    if blank == 0:
        lab_valid = lab > 0
    else:
        lab_valid = lab >= 0
    if label_lengths is not None:
        lab_len = label_lengths.astype(jnp.int32)
    else:
        lab_len = jnp.sum(lab_valid.astype(jnp.int32), axis=1)
    if data_lengths is not None:
        t_len = data_lengths.astype(jnp.int32)
    else:
        t_len = jnp.full((N,), T, dtype=jnp.int32)

    neg_inf = jnp.float32(-1e30)
    s_idx = jnp.arange(S)
    lab_pos = jnp.maximum((s_idx[None, :] - 1) // 2, 0)
    ext = jnp.where(s_idx[None, :] % 2 == 0, blank,
                    jnp.take_along_axis(lab, lab_pos, axis=1))  # (N, S)
    ext_m2 = jnp.concatenate(
        [jnp.full((N, 2), -1, jnp.int32), ext[:, :-2]], axis=1)
    allow_skip = (s_idx[None, :] % 2 == 1) & (ext != ext_m2)

    def lse3(a, b, c):
        m = jnp.maximum(jnp.maximum(a, b), c)
        m = jnp.maximum(m, neg_inf)  # avoid -inf - -inf
        return m + jnp.log(jnp.exp(a - m) + jnp.exp(b - m) + jnp.exp(c - m))

    def step(alpha, logp_t):
        emit = jnp.take_along_axis(logp_t, ext, axis=1)  # (N, S)
        a2 = jnp.concatenate(
            [jnp.full((N, 1), neg_inf), alpha[:, :-1]], axis=1)
        a3 = jnp.concatenate(
            [jnp.full((N, 2), neg_inf), alpha[:, :-2]], axis=1)
        a3 = jnp.where(allow_skip, a3, neg_inf)
        new = emit + lse3(alpha, a2, a3)
        return new, new

    # virtual pre-start state: probability mass only at s=0, no emission yet
    start = jnp.where(jnp.broadcast_to(s_idx[None, :] == 0, (N, S)),
                      0.0, neg_inf)
    _, alphas = jax.lax.scan(step, start, logp)  # (T, N, S)

    last = jnp.take_along_axis(
        alphas, (t_len - 1)[None, :, None].astype(jnp.int32), axis=0)[0]
    end1 = jnp.take_along_axis(last, (2 * lab_len)[:, None], axis=1)[:, 0]
    end2 = jnp.take_along_axis(
        last, jnp.maximum(2 * lab_len - 1, 0)[:, None], axis=1)[:, 0]
    # empty label (lab_len==0): only the all-blank path exists; don't count
    # the clamped duplicate end state twice
    end2 = jnp.where(lab_len > 0, end2, neg_inf)
    m = jnp.maximum(end1, end2)
    ll = m + jnp.log(jnp.exp(end1 - m) + jnp.exp(end2 - m))
    return -ll.astype(data.dtype)
