"""Registry-wide operator correctness sweep.

Every canonical op in ``mxtpu.ops.registry`` is either:

* **SPEC'd** here — forward-checked against an independent numpy reference
  (or a structural ``check``), and, when differentiable, gradient-checked
  against central finite differences through ``mxtpu.autograd``; or
* **SKIP'd** with an explicit reason — usually a pointer to the dedicated
  test file that covers it in depth, or a statement of why a generic
  numeric check does not apply (custom_vjp training grads, stochastic
  ops, factorizations with sign conventions).

``test_registry_fully_covered`` asserts this partition is *total* over the
registry, so a newly registered op fails CI until it is added here.

Reference model: ``tests/python/unittest/test_operator.py`` (5.4k lines of
per-op checks) — this file is the breadth tier; the dedicated test files
(test_operator/test_vision_ops/test_rnn/...) keep the depth tier.
"""
import math
import zlib

import numpy as np
import pytest

import mxtpu.autograd as ag
import mxtpu.ndarray as nd
from mxtpu.ops import registry

# --------------------------------------------------------------------------
# machinery
# --------------------------------------------------------------------------


def _seed(name):
    return zlib.crc32(name.encode()) % (2 ** 31)


def _canonical_ops():
    seen = {}
    for n in registry.list_ops():
        op = registry.get_op(n)
        seen.setdefault(op.name, op)
    return seen


class Spec:
    """Inputs + reference for one op.

    args : callable(rng) -> list of inputs (np arrays or scalars)
    params : static keyword params for the op call
    ref : callable(*np_args, **params) -> array or tuple of arrays
          compared elementwise to the op's (user) outputs; None = smoke
    check : callable(outs, args) doing custom asserts (e.g. statistical
            checks for samplers, reconstruction checks for factorizations)
    grad : False to disable the FD gradient check (requires reason)
    grad_args : explicit arg indices to differentiate (default: every
                float-typed array argument)
    """

    def __init__(self, args, params=None, ref=None, check=None, grad=None,
                 grad_args=None, reason=None, rtol=1e-4, atol=1e-5,
                 g_rtol=0.05, g_atol=5e-3):
        self.args = args
        self.params = params or {}
        self.ref = ref
        self.check = check
        self.grad = grad
        self.grad_args = grad_args
        self.reason = reason
        self.rtol, self.atol = rtol, atol
        self.g_rtol, self.g_atol = g_rtol, g_atol


def _to_nd(a):
    return nd.array(a) if isinstance(a, np.ndarray) else a


def _run(name, args, params):
    out = getattr(nd, name)(*[_to_nd(a) for a in args], **params)
    outs = out if isinstance(out, (list, tuple)) else [out]
    return [o.asnumpy() for o in outs]


GRAD_COORD_CAP = 10  # FD coords sampled per input (all if size <= cap)
FD_EPS = 1e-3


def _float_arg_indices(args):
    return [i for i, a in enumerate(args)
            if isinstance(a, np.ndarray) and a.dtype.kind == "f"]


# helper input factories ----------------------------------------------------

def u(r, *shape, lo=-1.0, hi=1.0):
    return r.uniform(lo, hi, shape).astype(np.float32)


def pos(r, *shape, lo=0.3, hi=2.0):
    return r.uniform(lo, hi, shape).astype(np.float32)


def away0(r, *shape, lo=0.2, hi=1.0):
    """Floats bounded away from 0 (kinks of relu/abs/sign/...)."""
    return (r.uniform(lo, hi, shape) *
            r.choice([-1.0, 1.0], shape)).astype(np.float32)


def distinct(r, *shape):
    """Distinct values (no ties for max/min/sort FD)."""
    n = int(np.prod(shape))
    vals = (np.arange(n) - n / 2.0) * 0.1 + r.uniform(-0.01, 0.01, n)
    return r.permutation(vals).reshape(shape).astype(np.float32)


def idx(r, *shape, high):
    return r.randint(0, high, shape).astype(np.int32)


def spd(r, n, batch=()):
    """Symmetric positive-definite matrix (cholesky-friendly)."""
    b = r.uniform(-1, 1, batch + (n, n))
    a = np.einsum("...ij,...kj->...ik", b, b) + n * np.eye(n)
    return a.astype(np.float32)


def lower_tri(r, n):
    m = np.tril(r.uniform(0.5, 1.5, (n, n))) + np.eye(n)
    return m.astype(np.float32)


# numpy reference helpers ---------------------------------------------------

def np_conv2d(x, w, b=None, stride=(1, 1), pad=(0, 0), dilate=(1, 1)):
    N, C, H, W = x.shape
    O, _, KH, KW = w.shape
    x = np.pad(x, ((0, 0), (0, 0), (pad[0], pad[0]), (pad[1], pad[1])))
    dkh = (KH - 1) * dilate[0] + 1
    dkw = (KW - 1) * dilate[1] + 1
    OH = (x.shape[2] - dkh) // stride[0] + 1
    OW = (x.shape[3] - dkw) // stride[1] + 1
    out = np.zeros((N, O, OH, OW), np.float64)
    for n in range(N):
        for o in range(O):
            for i in range(OH):
                for j in range(OW):
                    patch = x[n, :,
                              i * stride[0]:i * stride[0] + dkh:dilate[0],
                              j * stride[1]:j * stride[1] + dkw:dilate[1]]
                    out[n, o, i, j] = (patch * w[o]).sum()
    if b is not None:
        out += b.reshape(1, -1, 1, 1)
    return out.astype(np.float32)


def np_deconv2d(x, w, stride=(1, 1), pad=(0, 0)):
    N, C, H, W = x.shape
    _, O, KH, KW = w.shape
    OH = (H - 1) * stride[0] + KH - 2 * pad[0]
    OW = (W - 1) * stride[1] + KW - 2 * pad[1]
    full = np.zeros((N, O, (H - 1) * stride[0] + KH,
                     (W - 1) * stride[1] + KW), np.float64)
    for n in range(N):
        for c in range(C):
            for i in range(H):
                for j in range(W):
                    full[n, :, i * stride[0]:i * stride[0] + KH,
                         j * stride[1]:j * stride[1] + KW] += x[n, c, i, j] * w[c]
    out = full[:, :, pad[0]:pad[0] + OH, pad[1]:pad[1] + OW]
    return out.astype(np.float32)


def np_pool2d(x, kernel, pool_type="max", stride=None, pad=(0, 0),
              count_include_pad=True):
    stride = stride or kernel
    N, C, H, W = x.shape
    fill = -np.inf if pool_type == "max" else 0.0
    xp = np.pad(x, ((0, 0), (0, 0), (pad[0], pad[0]), (pad[1], pad[1])),
                constant_values=fill)
    OH = (xp.shape[2] - kernel[0]) // stride[0] + 1
    OW = (xp.shape[3] - kernel[1]) // stride[1] + 1
    out = np.zeros((N, C, OH, OW), np.float64)
    for i in range(OH):
        for j in range(OW):
            patch = xp[:, :, i * stride[0]:i * stride[0] + kernel[0],
                       j * stride[1]:j * stride[1] + kernel[1]]
            if pool_type == "max":
                out[:, :, i, j] = patch.max(axis=(2, 3))
            else:
                out[:, :, i, j] = patch.mean(axis=(2, 3))
    return out.astype(np.float32)


def np_softmax(x, axis=-1):
    e = np.exp(x - x.max(axis=axis, keepdims=True))
    return e / e.sum(axis=axis, keepdims=True)


def np_lrn(x, alpha, beta, knorm, nsize):
    N, C, H, W = x.shape
    out = np.zeros_like(x, np.float64)
    half = nsize // 2
    for c in range(C):
        lo, hi = max(0, c - half), min(C, c + half + 1)
        sq = (x[:, lo:hi] ** 2).sum(axis=1)
        out[:, c] = x[:, c] / (knorm + alpha / nsize * sq) ** beta
    return out.astype(np.float32)


def np_moe_ffn(x, gw, w1, b1, w2, b2, capacity_factor):
    """Top-1 experts with room for every token: each token's own expert's
    relu feed-forward times its gate probability, and the Switch loss."""
    p = np_softmax(x @ gw, -1)
    e = p.argmax(-1)
    h = np.maximum(np.einsum("td,tdh->th", x, w1[e]) + b1[e], 0)
    y = p.max(-1, keepdims=True) * (np.einsum("th,thd->td", h, w2[e]) + b2[e])
    frac = np.bincount(e, minlength=gw.shape[1]) / len(e)
    return y, np.array([gw.shape[1] * (frac * p.mean(0)).sum()])


def _vec(f):
    return np.vectorize(f, otypes=[np.float32])


# --------------------------------------------------------------------------
# SKIP list — ops not swept generically, with the reason / covering test
# --------------------------------------------------------------------------

SKIP = {
    "RNN": "fused multi-layer LSTM/GRU/vanilla kernel; depth-tested vs "
           "manual cell unrolls (fwd+grad) in tests/test_rnn.py",
    "Custom": "needs a user-registered python op; round-trip (fwd+bwd) "
              "covered in tests/test_custom_op.py",
    "_contrib_flash_attention": "Pallas kernel; fwd/bwd vs XLA attention in "
                                "tests/test_pallas_attention.py",
    "_contrib_gc_quantize_2bit": "2-bit gradient compression round-trip + "
                                 "error-feedback in tests/test_gradcomp.py",
    "_contrib_gc_dequantize_2bit": "see _contrib_gc_quantize_2bit",
    "cached_attention": "three outputs over a carried KV cache (write "
                        "offsets, ring window, grouped heads); prefill and "
                        "decode vs a dense numpy/jnp attention in "
                        "tests/test_serving_generate.py, "
                        "tests/test_decode_attention.py and "
                        "tests/test_grouped_attention.py",
    "moe_ffn_held": "integer load counters out and an expert_first offset "
                    "in; the held shares vs the reference's uncut layer in "
                    "tests/test_exaone_moe_serving.py"
                    "::test_eight_shares_add_up_to_the_uncut_layer",
    "latent_attention": "two outputs over a carried latent cache (write "
                        "offset in, next cache out), a kernel on one row a "
                        "sample; the per-head definition, chunked and "
                        "absorbed, in tests/test_latent_attention.py",
    "hyper_mix": "three outputs of two dtypes (the read in the streams' "
                 "dtype, the carried streams and write weights in float32); "
                 "the definition in float64 in tests/test_hyper_mix.py",
    "hyper_merge": "float32 operands from hyper_mix, the result in the "
                   "sub-layer's dtype; with hyper_mix in "
                   "tests/test_hyper_mix.py",
}

# --------------------------------------------------------------------------
# SPECS
# --------------------------------------------------------------------------

SPECS = {}


def S(name, *a, **kw):
    SPECS[name] = Spec(*a, **kw)


NO_FD_CUSTOM_GRAD = ("custom_vjp training gradient by design (loss/output "
                     "head); analytic grad asserted in "
                     "test_output_head_gradients")

# ---- elemwise unary (numpy-backed and mxtpu.ops.elemwise) ----------------

S("abs", lambda r: [away0(r, 3, 4)], ref=np.abs)
S("arccos", lambda r: [u(r, 3, 4, lo=-0.8, hi=0.8)], ref=np.arccos)
S("arccosh", lambda r: [u(r, 3, 4, lo=1.5, hi=3.0)], ref=np.arccosh)
S("arcsin", lambda r: [u(r, 3, 4, lo=-0.8, hi=0.8)], ref=np.arcsin)
S("arcsinh", lambda r: [u(r, 3, 4)], ref=np.arcsinh)
S("arctan", lambda r: [u(r, 3, 4)], ref=np.arctan)
S("arctanh", lambda r: [u(r, 3, 4, lo=-0.8, hi=0.8)], ref=np.arctanh)
S("cbrt", lambda r: [pos(r, 3, 4)], ref=np.cbrt)
S("ceil", lambda r: [u(r, 3, 4, lo=-3, hi=3)], ref=np.ceil)
S("cos", lambda r: [u(r, 3, 4)], ref=np.cos)
S("cosh", lambda r: [u(r, 3, 4)], ref=np.cosh)
S("degrees", lambda r: [u(r, 3, 4)], ref=np.degrees)
S("erf", lambda r: [u(r, 3, 4)], ref=_vec(math.erf), rtol=1e-4, atol=1e-5)
S("exp", lambda r: [u(r, 3, 4)], ref=np.exp)
S("expm1", lambda r: [u(r, 3, 4)], ref=np.expm1)
S("fix", lambda r: [u(r, 3, 4, lo=-3, hi=3)], ref=np.fix)
S("floor", lambda r: [u(r, 3, 4, lo=-3, hi=3)], ref=np.floor)
S("gamma", lambda r: [pos(r, 3, 4, lo=0.5, hi=3.0)], ref=_vec(math.gamma),
  rtol=1e-3, atol=1e-4)
S("gammaln", lambda r: [pos(r, 3, 4, lo=0.5, hi=3.0)], ref=_vec(math.lgamma),
  rtol=1e-3, atol=1e-4)
S("identity", lambda r: [u(r, 3, 4)], ref=lambda x: x)
S("log", lambda r: [pos(r, 3, 4)], ref=np.log)
S("log10", lambda r: [pos(r, 3, 4)], ref=np.log10)
S("log1p", lambda r: [u(r, 3, 4, lo=-0.5, hi=2.0)], ref=np.log1p)
S("log2", lambda r: [pos(r, 3, 4)], ref=np.log2)
S("logical_not", lambda r: [r.choice([0.0, 1.0, 2.0], (3, 4)).astype("f")],
  ref=lambda x: np.logical_not(x).astype(np.float32))
S("negative", lambda r: [u(r, 3, 4)], ref=np.negative)
S("radians", lambda r: [u(r, 3, 4, lo=-180, hi=180)], ref=np.radians)
S("rcbrt", lambda r: [pos(r, 3, 4)], ref=lambda x: 1.0 / np.cbrt(x))
S("reciprocal", lambda r: [away0(r, 3, 4, lo=0.5)], ref=lambda x: 1.0 / x)
S("relu", lambda r: [away0(r, 3, 4)], ref=lambda x: np.maximum(x, 0))
S("rint", lambda r: [u(r, 3, 4, lo=-3, hi=3)], ref=np.rint)
S("round", lambda r: [u(r, 3, 4, lo=-3, hi=3)],
  ref=lambda x: np.floor(np.abs(x) + 0.5) * np.sign(x))  # MXNet rounds half away from zero
S("rsqrt", lambda r: [pos(r, 3, 4)], ref=lambda x: 1.0 / np.sqrt(x))
S("sigmoid", lambda r: [u(r, 3, 4)], ref=lambda x: 1 / (1 + np.exp(-x)))
S("sign", lambda r: [away0(r, 3, 4)], ref=np.sign)
S("sin", lambda r: [u(r, 3, 4)], ref=np.sin)
S("sinh", lambda r: [u(r, 3, 4)], ref=np.sinh)
S("smooth_l1", lambda r: [u(r, 3, 4, lo=-2, hi=2)], params={"scalar": 1.0},
  ref=lambda x, scalar: np.where(np.abs(x) < 1.0 / scalar ** 2,
                                 0.5 * (scalar * x) ** 2,
                                 np.abs(x) - 0.5 / scalar ** 2))
S("softrelu", lambda r: [u(r, 3, 4)], ref=lambda x: np.log1p(np.exp(x)))
S("softsign", lambda r: [u(r, 3, 4)], ref=lambda x: x / (1 + np.abs(x)))
S("sqrt", lambda r: [pos(r, 3, 4)], ref=np.sqrt)
S("square", lambda r: [u(r, 3, 4)], ref=np.square)
S("tan", lambda r: [u(r, 3, 4)], ref=np.tan)
S("tanh", lambda r: [u(r, 3, 4)], ref=np.tanh)
S("trunc", lambda r: [u(r, 3, 4, lo=-3, hi=3)], ref=np.trunc)
S("clip", lambda r: [np.array([[-0.9, -0.2, 0.3, 0.8],
                               [0.1, -0.7, 0.9, -0.3]], np.float32)],
  params={"a_min": -0.5, "a_max": 0.5},
  ref=lambda x, a_min, a_max: np.clip(x, a_min, a_max))

# ---- tensor-scalar family (elemwise_binary_scalar_op_*.cc) ---------------

S("_plus_scalar", lambda r: [u(r, 3, 4)], params={"scalar": 1.5},
  ref=lambda x, scalar: x + scalar)
S("_minus_scalar", lambda r: [u(r, 3, 4)], params={"scalar": 1.5},
  ref=lambda x, scalar: x - scalar)
S("_rminus_scalar", lambda r: [u(r, 3, 4)], params={"scalar": 1.5},
  ref=lambda x, scalar: scalar - x)
S("_mul_scalar", lambda r: [u(r, 3, 4)], params={"scalar": 3.0},
  ref=lambda x, scalar: x * scalar)
S("_div_scalar", lambda r: [u(r, 3, 4)], params={"scalar": 2.0},
  ref=lambda x, scalar: x / scalar)
S("_rdiv_scalar", lambda r: [away0(r, 3, 4, lo=0.5)],
  params={"scalar": 2.0}, ref=lambda x, scalar: scalar / x)
S("_mod_scalar", lambda r: [pos(r, 3, 4, lo=2.1, hi=2.9)],
  params={"scalar": 0.8}, ref=lambda x, scalar: np.mod(x, scalar))
S("_rmod_scalar", lambda r: [pos(r, 3, 4, lo=0.7, hi=0.95)],
  params={"scalar": 2.5}, ref=lambda x, scalar: np.mod(scalar, x))
S("_power_scalar", lambda r: [pos(r, 3, 4)], params={"scalar": 2.0},
  ref=lambda x, scalar: np.power(x, scalar))
S("_rpower_scalar", lambda r: [u(r, 3, 4, lo=-2, hi=2)],
  params={"scalar": 2.0}, ref=lambda x, scalar: np.power(scalar, x))
S("_maximum_scalar", lambda r: [distinct(r, 3, 4)], params={"scalar": 0.1},
  ref=lambda x, scalar: np.maximum(x, scalar))
S("_minimum_scalar", lambda r: [distinct(r, 3, 4)], params={"scalar": 0.1},
  ref=lambda x, scalar: np.minimum(x, scalar))
S("_hypot_scalar", lambda r: [away0(r, 3, 4)], params={"scalar": 1.5},
  ref=lambda x, scalar: np.hypot(x, scalar))
for _sn, _sref in [
        ("_equal_scalar", np.equal), ("_not_equal_scalar", np.not_equal),
        ("_greater_scalar", np.greater),
        ("_greater_equal_scalar", np.greater_equal),
        ("_lesser_scalar", np.less), ("_lesser_equal_scalar", np.less_equal),
        ("_logical_and_scalar", np.logical_and),
        ("_logical_or_scalar", np.logical_or),
        ("_logical_xor_scalar", np.logical_xor)]:
    def _mk_sref(f):
        return lambda x, scalar: f(x, scalar).astype(np.float32)
    S(_sn, lambda r: [r.choice([0.0, 0.5, 1.0], (3, 4)).astype("f")],
      params={"scalar": 0.5}, ref=_mk_sref(_sref))

# ---- elemwise binary ------------------------------------------------------

S("broadcast_add", lambda r: [u(r, 3, 4), u(r, 1, 4)], ref=np.add)
S("broadcast_sub", lambda r: [u(r, 3, 4), u(r, 1, 4)], ref=np.subtract)
S("broadcast_mul", lambda r: [u(r, 3, 4), u(r, 1, 4)], ref=np.multiply)
S("broadcast_div", lambda r: [u(r, 3, 4), pos(r, 1, 4)], ref=np.divide)
S("broadcast_mod", lambda r: [pos(r, 3, 4, lo=2.1, hi=2.9),
                              pos(r, 1, 4, lo=0.7, hi=0.95)],
  ref=np.mod)
S("broadcast_power", lambda r: [pos(r, 3, 4), u(r, 1, 4, lo=-2, hi=2)],
  ref=np.power)
S("broadcast_maximum", lambda r: [distinct(r, 3, 4), distinct(r, 3, 4)],
  ref=np.maximum)
S("broadcast_minimum", lambda r: [distinct(r, 3, 4), distinct(r, 3, 4)],
  ref=np.minimum)
S("broadcast_hypot", lambda r: [away0(r, 3, 4), away0(r, 1, 4)],
  ref=np.hypot)
S("arctan2", lambda r: [away0(r, 3, 4), away0(r, 3, 4)], ref=np.arctan2)
S("broadcast_equal", lambda r: [r.randint(0, 2, (3, 4)).astype("f"),
                                r.randint(0, 2, (3, 4)).astype("f")],
  ref=lambda a, b: (a == b).astype(np.float32))
S("broadcast_not_equal", lambda r: [r.randint(0, 2, (3, 4)).astype("f"),
                                    r.randint(0, 2, (3, 4)).astype("f")],
  ref=lambda a, b: (a != b).astype(np.float32))
S("broadcast_greater", lambda r: [u(r, 3, 4), u(r, 3, 4)],
  ref=lambda a, b: (a > b).astype(np.float32))
S("broadcast_greater_equal", lambda r: [u(r, 3, 4), u(r, 3, 4)],
  ref=lambda a, b: (a >= b).astype(np.float32))
S("broadcast_lesser", lambda r: [u(r, 3, 4), u(r, 3, 4)],
  ref=lambda a, b: (a < b).astype(np.float32))
S("broadcast_lesser_equal", lambda r: [u(r, 3, 4), u(r, 3, 4)],
  ref=lambda a, b: (a <= b).astype(np.float32))
S("broadcast_logical_and", lambda r: [r.randint(0, 2, (3, 4)).astype("f"),
                                      r.randint(0, 2, (3, 4)).astype("f")],
  ref=lambda a, b: np.logical_and(a, b).astype(np.float32))
S("broadcast_logical_or", lambda r: [r.randint(0, 2, (3, 4)).astype("f"),
                                     r.randint(0, 2, (3, 4)).astype("f")],
  ref=lambda a, b: np.logical_or(a, b).astype(np.float32))
S("broadcast_logical_xor", lambda r: [r.randint(0, 2, (3, 4)).astype("f"),
                                      r.randint(0, 2, (3, 4)).astype("f")],
  ref=lambda a, b: np.logical_xor(a, b).astype(np.float32))
S("where", lambda r: [r.randint(0, 2, (3, 4)).astype("f"),
                      u(r, 3, 4), u(r, 3, 4)],
  ref=lambda c, x, y: np.where(c != 0, x, y), grad_args=[1, 2])
S("add_n", lambda r: [u(r, 3, 4), u(r, 3, 4), u(r, 3, 4)],
  ref=lambda *xs: sum(xs))

# ---- reductions / ordering ------------------------------------------------

S("sum", lambda r: [u(r, 3, 4)], params={"axis": 1},
  ref=lambda x, axis: x.sum(axis=axis))
S("mean", lambda r: [u(r, 3, 4)], params={"axis": 0, "keepdims": True},
  ref=lambda x, axis, keepdims: x.mean(axis=axis, keepdims=keepdims))
S("prod", lambda r: [pos(r, 3, 4, lo=0.5, hi=1.5)], params={"axis": 1},
  ref=lambda x, axis: x.prod(axis=axis))
S("max", lambda r: [distinct(r, 3, 4)], params={"axis": 1},
  ref=lambda x, axis: x.max(axis=axis))
S("min", lambda r: [distinct(r, 3, 4)], params={"axis": 1},
  ref=lambda x, axis: x.min(axis=axis))
S("nansum", lambda r: [u(r, 3, 4)], params={"axis": 1},
  ref=lambda x, axis: np.nansum(x, axis=axis))  # finite inputs: FD needs them
S("nanprod", lambda r: [pos(r, 3, 4, lo=0.5, hi=1.5)], params={"axis": 1},
  ref=lambda x, axis: np.nanprod(x, axis=axis))
S("norm", lambda r: [u(r, 3, 4)], params={"ord": 2, "axis": 1},
  ref=lambda x, ord, axis: np.sqrt((x ** 2).sum(axis=axis)))
S("argmax", lambda r: [distinct(r, 3, 4)], params={"axis": 1},
  ref=lambda x, axis: np.argmax(x, axis=axis).astype(np.float32))
S("argmin", lambda r: [distinct(r, 3, 4)], params={"axis": 1},
  ref=lambda x, axis: np.argmin(x, axis=axis).astype(np.float32))
S("argmax_channel", lambda r: [distinct(r, 3, 4)],
  ref=lambda x: np.argmax(x, axis=1).astype(np.float32))
S("argsort", lambda r: [distinct(r, 2, 5)],
  ref=lambda x: np.argsort(x, axis=-1).astype(np.float32))
S("sort", lambda r: [distinct(r, 2, 5)], ref=lambda x: np.sort(x, axis=-1))
S("topk", lambda r: [distinct(r, 2, 5)], params={"k": 2, "ret_typ": "value"},
  ref=lambda x, k, ret_typ: np.sort(x, axis=-1)[..., ::-1][..., :k])

# ---- shape / index --------------------------------------------------------

S("cast", lambda r: [u(r, 3, 4)], params={"dtype": "float64"},
  ref=lambda x, dtype: x.astype(dtype))
S("concat", lambda r: [u(r, 2, 3), u(r, 2, 4)], params={"dim": 1},
  ref=lambda a, b, dim: np.concatenate([a, b], axis=dim))
S("flatten", lambda r: [u(r, 2, 3, 4)], ref=lambda x: x.reshape(2, 12))
S("reshape", lambda r: [u(r, 2, 6)], params={"shape": (3, 4)},
  ref=lambda x, shape: x.reshape(shape))
S("reshape_like", lambda r: [u(r, 2, 6), u(r, 3, 4)],
  ref=lambda x, y: x.reshape(y.shape), grad_args=[0])
S("expand_dims", lambda r: [u(r, 3, 4)], params={"axis": 1},
  ref=lambda x, axis: np.expand_dims(x, axis))
S("squeeze", lambda r: [u(r, 3, 1, 4)], params={"axis": 1},
  ref=lambda x, axis: np.squeeze(x, axis))
S("transpose", lambda r: [u(r, 2, 3, 4)], params={"axes": (2, 0, 1)},
  ref=lambda x, axes: np.transpose(x, axes))
S("swapaxes", lambda r: [u(r, 2, 3, 4)], params={"dim1": 0, "dim2": 2},
  ref=lambda x, dim1, dim2: np.swapaxes(x, dim1, dim2))
S("tile", lambda r: [u(r, 2, 3)], params={"reps": (2, 2)},
  ref=lambda x, reps: np.tile(x, reps))
S("repeat", lambda r: [u(r, 2, 3)], params={"repeats": 2, "axis": 1},
  ref=lambda x, repeats, axis: np.repeat(x, repeats, axis))
S("reverse", lambda r: [u(r, 3, 4)], params={"axis": 1},
  ref=lambda x, axis: np.flip(x, axis))
S("slice", lambda r: [u(r, 4, 5)], params={"begin": (1, 0), "end": (3, 4)},
  ref=lambda x, begin, end: x[1:3, 0:4])
S("slice_axis", lambda r: [u(r, 4, 5)],
  params={"axis": 1, "begin": 1, "end": 4},
  ref=lambda x, axis, begin, end: x[:, 1:4])
S("slice_like", lambda r: [u(r, 4, 5), u(r, 2, 3)],
  ref=lambda x, y: x[:2, :3], grad_args=[0])
S("take", lambda r: [u(r, 4, 3), idx(r, 5, high=4)],
  ref=lambda a, i: a[i])
S("batch_take", lambda r: [u(r, 3, 4), idx(r, 3, high=4)],
  ref=lambda a, i: a[np.arange(3), i])
S("gather_nd", lambda r: [u(r, 4, 5), idx(r, 2, 3, high=4)],
  ref=lambda d, i: d[i[0], i[1]])
S("scatter_nd", lambda r: [u(r, 3), np.array([[0, 2, 0]], np.int32)],
  params={"shape": (4,)},
  ref=lambda d, i, shape: np.array(
      [d[0] + d[2], 0, d[1], 0], np.float32))
S("one_hot", lambda r: [idx(r, 5, high=4)],
  params={"depth": 4, "on_value": 2.0, "off_value": -1.0},
  ref=lambda i, depth, on_value, off_value:
      np.where(np.arange(depth)[None, :] == i[:, None],
               on_value, off_value).astype(np.float32))
S("pick", lambda r: [u(r, 3, 4), idx(r, 3, high=4).astype(np.float32)],
  params={"axis": 1},
  ref=lambda d, i, axis: d[np.arange(3), i.astype(np.int64)],
  grad_args=[0])
S("depth_to_space", lambda r: [u(r, 1, 8, 2, 2)], params={"block_size": 2},
  grad_args=[0],
  ref=lambda x, block_size: x.reshape(1, 2, 2, 2, 2, 2)
      .transpose(0, 3, 4, 1, 5, 2).reshape(1, 2, 4, 4))
S("space_to_depth", lambda r: [u(r, 1, 2, 4, 4)], params={"block_size": 2},
  grad_args=[0],
  ref=lambda x, block_size: x.reshape(1, 2, 2, 2, 2, 2)
      .transpose(0, 3, 5, 1, 2, 4).reshape(1, 8, 2, 2))
S("diag", lambda r: [u(r, 4, 4)], ref=lambda x: np.diag(x))
S("stack", lambda r: [u(r, 3, 4), u(r, 3, 4)], params={"axis": 1},
  ref=lambda a, b, axis: np.stack([a, b], axis=axis))
S("split", lambda r: [u(r, 2, 6)], params={"num_outputs": 3, "axis": 1},
  ref=lambda x, num_outputs, axis: tuple(np.split(x, num_outputs, axis)))
S("broadcast_axis", lambda r: [u(r, 3, 1)], params={"axis": 1, "size": 4},
  ref=lambda x, axis, size: np.broadcast_to(x, (3, 4)))
S("broadcast_like", lambda r: [u(r, 3, 1), u(r, 3, 4)],
  ref=lambda x, y: np.broadcast_to(x, y.shape), grad_args=[0])
S("broadcast_to", lambda r: [u(r, 3, 1)], params={"shape": (3, 4)},
  ref=lambda x, shape: np.broadcast_to(x, shape))
S("pad", lambda r: [u(r, 1, 2, 3, 3)],
  params={"mode": "constant",
          "pad_width": (0, 0, 0, 0, 1, 1, 1, 1), "constant_value": 0.5},
  ref=lambda x, mode, pad_width, constant_value:
      np.pad(x, ((0, 0), (0, 0), (1, 1), (1, 1)),
             constant_values=constant_value))
S("ones_like", lambda r: [u(r, 3, 4)], ref=np.ones_like)
S("zeros_like", lambda r: [u(r, 3, 4)], ref=np.zeros_like)
S("_ones", lambda r: [], params={"shape": (3, 4)},
  ref=lambda shape: np.ones(shape, np.float32))
S("_zeros", lambda r: [], params={"shape": (3, 4)},
  ref=lambda shape: np.zeros(shape, np.float32))
S("shape_array", lambda r: [u(r, 3, 4)],
  ref=lambda x: np.array(x.shape, np.int64))
S("size_array", lambda r: [u(r, 3, 4)],
  ref=lambda x: np.array([x.size], np.int64))
S("_index", lambda r: [u(r, 4, 5)], params={"key": (slice(1, 3),)},
  ref=lambda x, key: x[key])
S("Crop", lambda r: [u(r, 1, 2, 6, 6)],
  params={"offset": (1, 1), "h_w": (4, 4)},
  ref=lambda x, offset, h_w: x[:, :, 1:5, 1:5])

# ---- linalg ---------------------------------------------------------------

S("dot", lambda r: [u(r, 3, 4), u(r, 4, 5)], ref=lambda a, b: a @ b)
S("batch_dot", lambda r: [u(r, 2, 3, 4), u(r, 2, 4, 5)],
  ref=lambda a, b: a @ b)
S("khatri_rao", lambda r: [u(r, 2, 4), u(r, 3, 4)],
  ref=lambda a, b: np.einsum("ik,jk->ijk", a, b).reshape(6, 4))
S("linalg_gemm", lambda r: [u(r, 3, 4), u(r, 4, 5), u(r, 3, 5)],
  params={"alpha": 2.0, "beta": 0.5},
  ref=lambda a, b, c, alpha, beta: alpha * (a @ b) + beta * c)
S("linalg_gemm2", lambda r: [u(r, 3, 4), u(r, 4, 5)], params={"alpha": 1.5},
  ref=lambda a, b, alpha: alpha * (a @ b))
S("linalg_syrk", lambda r: [u(r, 3, 4)], params={"alpha": 1.0},
  ref=lambda a, alpha: alpha * (a @ a.T))
S("linalg_trmm", lambda r: [lower_tri(r, 3), u(r, 3, 4)],
  ref=lambda a, b: np.tril(a) @ b)
S("linalg_trsm", lambda r: [lower_tri(r, 3), u(r, 3, 4)],
  ref=lambda a, b: np.linalg.solve(np.tril(a), b))
S("linalg_sumlogdiag", lambda r: [spd(r, 3)],
  ref=lambda a: np.log(np.diag(a)).sum().reshape(1,))
S("linalg_potrf", lambda r: [spd(r, 3)],
  ref=lambda a: np.linalg.cholesky(a),
  grad=False, reason="FD through a factorization is numerically unstable "
                     "(perturbation breaks SPD); forward vs np.linalg")
S("linalg_potri", lambda r: [np.linalg.cholesky(spd(r, 3))
                             .astype(np.float32)],
  ref=lambda l: np.linalg.inv(l @ l.T),  # potri: inv(A) from A's factor L
  grad=False, reason="see linalg_potrf", rtol=1e-3, atol=1e-4)
S("linalg_gelqf", lambda r: [u(r, 3, 5)],
  check=lambda outs, args: (
      np.testing.assert_allclose(outs[0] @ outs[1], args[0],
                                 rtol=1e-4, atol=1e-5),
      np.testing.assert_allclose(outs[1] @ outs[1].T, np.eye(3),
                                 rtol=1e-4, atol=1e-5)),
  grad=False, reason="LQ factors are sign/rotation-convention dependent; "
                     "checked by reconstruction (L@Q==A, Q orthonormal)")
S("linalg_syevd", lambda r: [spd(r, 3)],
  check=lambda outs, args: np.testing.assert_allclose(
      outs[0].T * outs[1] @ outs[0],
      args[0], rtol=1e-3, atol=1e-4),
  grad=False, reason="eigenvector sign conventions; checked by "
                     "reconstruction U^T diag(L) U == A")

# ---- NN core --------------------------------------------------------------

S("Activation", lambda r: [u(r, 3, 4)], params={"act_type": "tanh"},
  ref=lambda x, act_type: np.tanh(x))
S("FullyConnected", lambda r: [u(r, 2, 3), u(r, 4, 3), u(r, 4)],
  params={"num_hidden": 4},
  ref=lambda x, w, b, num_hidden: x @ w.T + b)
S("Convolution",
  lambda r: [u(r, 1, 2, 5, 5), u(r, 3, 2, 3, 3), u(r, 3)],
  params={"kernel": (3, 3), "num_filter": 3, "pad": (1, 1), "stride": (2, 2)},
  ref=lambda x, w, b, kernel, num_filter, pad, stride:
      np_conv2d(x, w, b, stride=stride, pad=pad),
  rtol=1e-3, atol=1e-4)
S("Deconvolution",
  lambda r: [u(r, 1, 2, 4, 4), u(r, 2, 3, 3, 3)],
  params={"kernel": (3, 3), "num_filter": 3, "stride": (2, 2), "pad": (1, 1)},
  ref=lambda x, w, kernel, num_filter, stride, pad:
      np_deconv2d(x, w, stride=stride, pad=pad),
  rtol=1e-3, atol=1e-4)
S("Pooling", lambda r: [distinct(r, 1, 2, 4, 4)],
  params={"kernel": (2, 2), "pool_type": "max", "stride": (2, 2)},
  ref=lambda x, kernel, pool_type, stride:
      np_pool2d(x, kernel, pool_type, stride))
S("BatchNorm",
  lambda r: [u(r, 2, 3, 4), pos(r, 3), u(r, 3), u(r, 3), pos(r, 3)],
  params={"fix_gamma": False, "use_global_stats": True, "eps": 1e-3},
  ref=lambda x, g, b, mm, mv, fix_gamma, use_global_stats, eps:
      (x - mm[None, :, None]) / np.sqrt(mv[None, :, None] + eps)
      * g[None, :, None] + b[None, :, None],
  grad_args=[0, 1, 2], rtol=1e-3, atol=1e-4)
S("LayerNorm", lambda r: [u(r, 3, 4), pos(r, 4), u(r, 4)],
  params={"eps": 1e-5},
  ref=lambda x, g, b, eps: (x - x.mean(-1, keepdims=True)) /
      np.sqrt(x.var(-1, keepdims=True) + eps) * g + b,
  rtol=1e-3, atol=1e-4)
S("RMSNorm", lambda r: [u(r, 3, 4), pos(r, 4)], params={"eps": 1e-5},
  ref=lambda x, g, eps: x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * g,
  rtol=1e-3, atol=1e-4)
# capacity_factor = the number of experts: no token is dropped
S("moe_ffn", lambda r: [u(r, 6, 4), u(r, 4, 3), u(r, 3, 4, 5), u(r, 3, 5),
                        u(r, 3, 5, 4), u(r, 3, 4)],
  params={"capacity_factor": 3.0}, ref=np_moe_ffn, rtol=1e-3, atol=1e-4)
S("InstanceNorm", lambda r: [u(r, 2, 3, 5), pos(r, 3), u(r, 3)],
  params={"eps": 1e-3},
  ref=lambda x, g, b, eps: (x - x.mean(-1, keepdims=True)) /
      np.sqrt(x.var(-1, keepdims=True) + eps) * g[None, :, None] +
      b[None, :, None],
  rtol=1e-3, atol=1e-4)
S("L2Normalization", lambda r: [u(r, 2, 3, 4)], params={"eps": 1e-10},
  ref=lambda x, eps: x / np.sqrt((x ** 2).sum(axis=(1, 2), keepdims=True)
                                 + eps),
  rtol=1e-3, atol=1e-4)
S("LRN", lambda r: [u(r, 1, 4, 3, 3)],
  params={"alpha": 1e-2, "beta": 0.75, "knorm": 2.0, "nsize": 3},
  ref=lambda x, alpha, beta, knorm, nsize: np_lrn(x, alpha, beta, knorm,
                                                  nsize),
  rtol=1e-3, atol=1e-4)
S("softmax", lambda r: [u(r, 3, 4)], params={"axis": -1},
  ref=lambda x, axis: np_softmax(x, axis))
S("log_softmax", lambda r: [u(r, 3, 4)], params={"axis": -1},
  ref=lambda x, axis: np.log(np_softmax(x, axis)))
S("SoftmaxActivation", lambda r: [u(r, 3, 4)],
  ref=lambda x: np_softmax(x, -1))
S("softmax_cross_entropy", lambda r: [u(r, 3, 4),
                                      idx(r, 3, high=4).astype(np.float32)],
  ref=lambda x, y: np.array(
      [-np.log(np_softmax(x, -1))[np.arange(3), y.astype(np.int64)].sum()],
      np.float32),
  grad_args=[0], rtol=1e-3, atol=1e-4)
S("Embedding", lambda r: [idx(r, 2, 3, high=5).astype(np.float32),
                          u(r, 5, 4)],
  params={"input_dim": 5, "output_dim": 4},
  ref=lambda i, w, input_dim, output_dim: w[i.astype(np.int64)],
  grad_args=[1])
S("Dropout", lambda r: [u(r, 3, 4)], params={"p": 0.5},
  ref=lambda x, p: x,  # eval mode = identity
  grad=False, reason="stochastic in train mode (per-call Bernoulli mask); "
                     "eval-mode identity is checked; masked-grad behavior "
                     "in tests/test_gluon dropout cases")
S("LeakyReLU", lambda r: [away0(r, 3, 4)],
  params={"act_type": "leaky", "slope": 0.25},
  ref=lambda x, act_type, slope: np.where(x > 0, x, slope * x))
S("BlockGrad", lambda r: [u(r, 3, 4)], ref=lambda x: x,
  grad=False, reason="gradient-blocking by design; zero-grad asserted in "
                     "test_blockgrad_blocks_gradient")
S("IdentityAttachKLSparseReg", lambda r: [u(r, 3, 4, lo=0.05, hi=0.95)],
  ref=lambda x: x,
  grad=False, reason="identity with attached KL regularizer gradient by "
                     "design; fwd identity checked")
S("UpSampling", lambda r: [u(r, 1, 2, 3, 3)],
  params={"scale": 2, "sample_type": "nearest"},
  ref=lambda x, scale, sample_type:
      x.repeat(scale, axis=2).repeat(scale, axis=3))
S("ctc_loss", lambda r: [u(r, 5, 2, 4), np.array([[1, 2], [3, 1]],
                                                 np.float32)],
  check=lambda outs, args: (
      # CTC loss is a positive scalar per batch element
      np.testing.assert_equal(outs[0].shape, (2,)),
      np.testing.assert_array_less(0.0, outs[0])),
  grad_args=[0], g_rtol=0.08, g_atol=1e-2)
S("MakeLoss", lambda r: [pos(r, 3)],
  ref=lambda x: x,
  grad=False, reason=NO_FD_CUSTOM_GRAD)
S("SoftmaxOutput", lambda r: [u(r, 3, 4), idx(r, 3, high=4).astype("f")],
  ref=lambda x, y: np_softmax(x, -1),
  grad=False, reason=NO_FD_CUSTOM_GRAD)
S("LinearRegressionOutput", lambda r: [u(r, 3, 4), u(r, 3, 4)],
  ref=lambda x, y: x, grad=False, reason=NO_FD_CUSTOM_GRAD)
S("MAERegressionOutput", lambda r: [u(r, 3, 4), u(r, 3, 4)],
  ref=lambda x, y: x, grad=False, reason=NO_FD_CUSTOM_GRAD)
S("LogisticRegressionOutput", lambda r: [u(r, 3, 4), u(r, 3, 4)],
  ref=lambda x, y: 1 / (1 + np.exp(-x)), grad=False,
  reason=NO_FD_CUSTOM_GRAD)
S("SVMOutput", lambda r: [u(r, 3, 4), idx(r, 3, high=4).astype("f")],
  ref=lambda x, y: x, grad=False, reason=NO_FD_CUSTOM_GRAD)

# ---- vision / spatial -----------------------------------------------------

S("BilinearSampler", lambda r: [u(r, 1, 2, 5, 5), u(r, 1, 2, 4, 4, lo=-0.7,
                                                    hi=0.7)],
  g_rtol=0.08, g_atol=1e-2)
S("GridGenerator", lambda r: [np.array([[1.1, 0.1, 0.05,
                                         -0.1, 0.9, -0.05]], np.float32)],
  params={"transform_type": "affine", "target_shape": (4, 4)},
  g_rtol=0.08, g_atol=1e-2)
S("SpatialTransformer", lambda r: [u(r, 1, 2, 5, 5),
                                   np.array([[1.0, 0.1, 0.05,
                                              -0.1, 0.9, -0.05]],
                                            np.float32)],
  params={"target_shape": (4, 4)}, g_rtol=0.08, g_atol=1e-2)
S("ROIPooling", lambda r: [distinct(r, 1, 2, 6, 6),
                           np.array([[0, 0, 0, 3, 3],
                                     [0, 1, 1, 5, 5]], np.float32)],
  params={"pooled_size": (2, 2), "spatial_scale": 1.0},
  grad_args=[0], g_rtol=0.08, g_atol=1e-2)
S("Correlation", lambda r: [u(r, 1, 2, 5, 5), u(r, 1, 2, 5, 5)],
  params={"kernel_size": 1, "max_displacement": 1},
  g_rtol=0.08, g_atol=1e-2)
S("SequenceLast", lambda r: [u(r, 4, 3, 2),
                             np.array([2, 4, 3], np.float32)],
  params={"use_sequence_length": True},
  ref=lambda d, sl, use_sequence_length:
      d[sl.astype(np.int64) - 1, np.arange(3)],
  grad_args=[0])
S("SequenceMask", lambda r: [u(r, 4, 3, 2), np.array([2, 4, 3], np.float32)],
  params={"use_sequence_length": True, "value": -1.0},
  ref=lambda d, sl, use_sequence_length, value: np.where(
      np.arange(4)[:, None, None] < sl.astype(np.int64)[None, :, None],
      d, value),
  grad_args=[0])
S("SequenceReverse", lambda r: [u(r, 4, 3, 2),
                                np.array([2, 4, 3], np.float32)],
  params={"use_sequence_length": True},
  ref=lambda d, sl, use_sequence_length: _np_seq_reverse(d, sl),
  grad_args=[0])
S("_contrib_PSROIPooling",
  lambda r: [u(r, 1, 8, 6, 6), np.array([[0, 0, 0, 4, 4]], np.float32)],
  params={"output_dim": 2, "pooled_size": 2, "spatial_scale": 1.0},
  grad_args=[0], g_rtol=0.08, g_atol=1e-2)
S("_contrib_DeformableConvolution",
  lambda r: [u(r, 1, 2, 5, 5), u(r, 1, 18, 3, 3, lo=-0.1, hi=0.1),
             u(r, 3, 2, 3, 3)],
  params={"kernel": (3, 3), "num_filter": 3, "no_bias": True},
  grad_args=[0, 2], g_rtol=0.08, g_atol=1e-2)
S("_contrib_DeformablePSROIPooling",
  lambda r: [u(r, 1, 8, 6, 6), np.array([[0, 0, 0, 4, 4]], np.float32)],
  params={"output_dim": 2, "pooled_size": 2, "group_size": 2,
          "spatial_scale": 1.0, "no_trans": True},
  grad_args=[0], g_rtol=0.08, g_atol=1e-2)
S("_contrib_MultiBoxPrior", lambda r: [u(r, 1, 3, 4, 4)],
  params={"sizes": (0.5, 0.3), "ratios": (1.0, 2.0)},
  check=lambda outs, args: (
      np.testing.assert_equal(outs[0].shape[-1], 4),
      np.testing.assert_array_less(outs[0], 1.5)))
S("_contrib_MultiBoxTarget",
  lambda r: [nd.contrib.MultiBoxPrior(nd.array(u(r, 1, 3, 4, 4)),
                                      sizes=(0.5,)).asnumpy(),
             np.array([[[0, 0.1, 0.1, 0.6, 0.6]]], np.float32),
             u(r, 1, 2, 16)],
  check=lambda outs, args: np.testing.assert_equal(len(outs), 3))
S("_contrib_MultiBoxDetection",
  lambda r: [np_softmax(u(r, 1, 2, 16), 1),
             u(r, 1, 64, lo=-0.1, hi=0.1),
             np.clip(np.sort(u(r, 1, 16, 4, lo=0.1, hi=0.9), axis=-1), 0, 1)],
  check=lambda outs, args: np.testing.assert_equal(outs[0].shape[-1], 6))
S("_contrib_Proposal",
  lambda r: [np_softmax(u(r, 1, 24, 4, 4).reshape(1, 2, 12, 4, 4), 1)
             .reshape(1, 24, 4, 4),
             u(r, 1, 48, 4, 4, lo=-0.1, hi=0.1),
             np.array([[64, 64, 1]], np.float32)],
  params={"rpn_pre_nms_top_n": 12, "rpn_post_nms_top_n": 4,
          "rpn_min_size": 1},
  check=lambda outs, args: np.testing.assert_equal(outs[0].shape[-1], 5))
S("_contrib_MultiProposal",
  lambda r: [np_softmax(u(r, 2, 24, 4, 4).reshape(2, 2, 12, 4, 4), 1)
             .reshape(2, 24, 4, 4),
             u(r, 2, 48, 4, 4, lo=-0.1, hi=0.1),
             np.array([[64, 64, 1], [64, 64, 1]], np.float32)],
  params={"rpn_pre_nms_top_n": 12, "rpn_post_nms_top_n": 4,
          "rpn_min_size": 1},
  check=lambda outs, args: np.testing.assert_equal(outs[0].shape[-1], 5))
S("_contrib_box_iou",
  lambda r: [np.array([[0, 0, 2, 2], [1, 1, 3, 3]], np.float32),
             np.array([[0, 0, 2, 2]], np.float32)],
  ref=lambda a, b: np.array([[1.0], [1.0 / 7.0]], np.float32))
S("_contrib_box_nms",
  lambda r: [np.array([[[0, 0.9, 0, 0, 2, 2],
                        [0, 0.8, 0.1, 0.1, 2, 2],
                        [0, 0.7, 5, 5, 7, 7]]], np.float32)],
  params={"overlap_thresh": 0.5, "coord_start": 2, "score_index": 1,
          "id_index": 0},
  check=lambda outs, args: (
      # the heavily-overlapping second box is suppressed (score -> -1)
      np.testing.assert_equal(outs[0].shape, (1, 3, 6)),
      np.testing.assert_equal((outs[0][0, :, 1] < 0).sum(), 1)))
S("_contrib_bipartite_matching",
  lambda r: [np.array([[[0.9, 0.1], [0.2, 0.8]]], np.float32)],
  params={"threshold": 0.05},
  check=lambda outs, args: np.testing.assert_allclose(
      outs[0][0], np.array([0.0, 1.0], np.float32)))

# ---- random (statistical forward checks; no gradients) --------------------

_N = 4000


def _moments(outs, mean, std, tol=0.15):
    x = outs[0].astype(np.float64)
    assert abs(x.mean() - mean) < tol * max(1.0, abs(mean) + std), \
        (x.mean(), mean)
    assert abs(x.std() - std) < tol * max(1.0, std), (x.std(), std)


S("random_uniform", lambda r: [], params={"low": -1.0, "high": 3.0,
                                          "shape": (_N,)},
  check=lambda outs, args: _moments(outs, 1.0, 4.0 / math.sqrt(12)))
S("random_normal", lambda r: [], params={"loc": 2.0, "scale": 3.0,
                                         "shape": (_N,)},
  check=lambda outs, args: _moments(outs, 2.0, 3.0))
S("random_exponential", lambda r: [], params={"lam": 2.0, "shape": (_N,)},
  check=lambda outs, args: _moments(outs, 0.5, 0.5))
S("random_gamma", lambda r: [], params={"alpha": 3.0, "beta": 2.0,
                                        "shape": (_N,)},
  check=lambda outs, args: _moments(outs, 6.0, math.sqrt(12.0)))
S("random_poisson", lambda r: [], params={"lam": 4.0, "shape": (_N,)},
  check=lambda outs, args: _moments(outs, 4.0, 2.0))
S("random_negative_binomial", lambda r: [],
  params={"k": 3, "p": 0.5, "shape": (_N,)},
  check=lambda outs, args: _moments(outs, 3.0, math.sqrt(6.0), tol=0.2))
S("random_generalized_negative_binomial", lambda r: [],
  params={"mu": 2.0, "alpha": 0.5, "shape": (_N,)},
  check=lambda outs, args: _moments(outs, 2.0, math.sqrt(2 + 0.5 * 4),
                                    tol=0.2))
S("random_randint", lambda r: [], params={"low": 2, "high": 8,
                                          "shape": (_N,)},
  check=lambda outs, args: (
      np.testing.assert_array_less(outs[0], 8),
      np.testing.assert_array_less(1, outs[0] + 1e-6),
      _moments(outs, 4.5, math.sqrt(35 / 12.0), tol=0.2)))
S("sample_uniform", lambda r: [np.array([0.0, 10.0], np.float32),
                               np.array([1.0, 20.0], np.float32)],
  params={"shape": (_N,)},
  check=lambda outs, args: (
      _moments([outs[0][0]], 0.5, 1.0 / math.sqrt(12)),
      _moments([outs[0][1]], 15.0, 10.0 / math.sqrt(12))))
S("sample_normal", lambda r: [np.array([0.0, 5.0], np.float32),
                              np.array([1.0, 2.0], np.float32)],
  params={"shape": (_N,)},
  check=lambda outs, args: (
      _moments([outs[0][0]], 0.0, 1.0),
      _moments([outs[0][1]], 5.0, 2.0)))
S("sample_gamma", lambda r: [np.array([2.0], np.float32),
                             np.array([3.0], np.float32)],
  params={"shape": (_N,)},
  check=lambda outs, args: _moments([outs[0][0]], 6.0, math.sqrt(18.0)))
S("sample_exponential", lambda r: [np.array([2.0, 0.5], np.float32)],
  params={"shape": (_N,)},
  check=lambda outs, args: (
      _moments([outs[0][0]], 0.5, 0.5),
      _moments([outs[0][1]], 2.0, 2.0)))
S("sample_poisson", lambda r: [np.array([4.0, 9.0], np.float32)],
  params={"shape": (_N,)},
  check=lambda outs, args: (
      _moments([outs[0][0]], 4.0, 2.0),
      _moments([outs[0][1]], 9.0, 3.0)))
S("sample_negative_binomial", lambda r: [np.array([3.0], np.float32),
                                         np.array([0.5], np.float32)],
  params={"shape": (_N,)},
  check=lambda outs, args: _moments([outs[0][0]], 3.0, math.sqrt(6.0),
                                    tol=0.2))
S("sample_generalized_negative_binomial",
  lambda r: [np.array([2.0], np.float32), np.array([0.5], np.float32)],
  params={"shape": (_N,)},
  check=lambda outs, args: _moments([outs[0][0]], 2.0,
                                    math.sqrt(2 + 0.5 * 4), tol=0.2))
S("sample_multinomial", lambda r: [np.array([[0.7, 0.2, 0.1],
                                             [0.05, 0.05, 0.9]], np.float32)],
  params={"shape": (_N,)},
  check=lambda outs, args: (
      np.testing.assert_array_less(outs[0], 3),
      np.testing.assert_(abs((outs[0][0] == 0).mean() - 0.7) < 0.1),
      np.testing.assert_(abs((outs[0][1] == 2).mean() - 0.9) < 0.1)))
S("shuffle", lambda r: [np.arange(24, dtype=np.float32).reshape(24)],
  check=lambda outs, args: np.testing.assert_allclose(
      np.sort(outs[0]), np.sort(args[0])))

# ---- optimizer update ops -------------------------------------------------


def _clip(g, c):
    return np.clip(g, -c, c) if c >= 0 else g


def _ref_sgd(w, g, lr, wd=0.0, rescale_grad=1.0, clip_gradient=-1.0,
             lazy_update=True):
    return w - lr * (_clip(g * rescale_grad, clip_gradient) + wd * w)


def _ref_sgd_mom(w, g, m, lr, momentum=0.0, wd=0.0, rescale_grad=1.0,
                 clip_gradient=-1.0, lazy_update=True):
    gg = _clip(g * rescale_grad, clip_gradient) + wd * w
    m2 = momentum * m - lr * gg
    return w + m2, m2


def _ref_adam(w, g, mean, var, lr, beta1=0.9, beta2=0.999, epsilon=1e-8,
              wd=0.0, rescale_grad=1.0, clip_gradient=-1.0,
              lazy_update=True):
    gg = _clip(g * rescale_grad, clip_gradient) + wd * w
    m2 = beta1 * mean + (1 - beta1) * gg
    v2 = beta2 * var + (1 - beta2) * gg ** 2
    return w - lr * m2 / (np.sqrt(v2) + epsilon), m2, v2


def _ref_rmsprop(w, g, n, lr, gamma1=0.95, epsilon=1e-8, wd=0.0,
                 rescale_grad=1.0, clip_gradient=-1.0, clip_weights=-1.0):
    gg = _clip(g * rescale_grad, clip_gradient) + wd * w
    n2 = (1 - gamma1) * gg ** 2 + gamma1 * n
    w2 = w - lr * gg / np.sqrt(n2 + epsilon)
    return (np.clip(w2, -clip_weights, clip_weights)
            if clip_weights > 0 else w2), n2


def _ref_adagrad(w, g, h, lr=None, epsilon=1e-7, wd=0.0, rescale_grad=1.0,
                 clip_gradient=-1.0):
    gg = _clip(g * rescale_grad, clip_gradient)
    h2 = h + gg ** 2
    return w - lr * (gg / np.sqrt(h2 + epsilon) + wd * w), h2


OPTIM_NO_GRAD = dict(grad=False,
                     reason="in-place optimizer update rule, not a "
                            "differentiable graph op (reference runs these "
                            "with kNullOp grads)")

S("sgd_update", lambda r: [u(r, 3, 4), u(r, 3, 4)],
  params={"lr": 0.1, "wd": 0.01}, ref=_ref_sgd, **OPTIM_NO_GRAD)
S("sgd_mom_update", lambda r: [u(r, 3, 4), u(r, 3, 4), u(r, 3, 4)],
  params={"lr": 0.1, "momentum": 0.9, "wd": 0.01}, ref=_ref_sgd_mom,
  **OPTIM_NO_GRAD)
S("mp_sgd_update",
  lambda r: [u(r, 3, 4).astype(np.float16), u(r, 3, 4).astype(np.float16),
             u(r, 3, 4)],
  params={"lr": 0.1, "wd": 0.01},
  ref=lambda w, g, w32, lr, wd: (
      _ref_sgd(w32, g.astype(np.float32), lr, wd).astype(np.float16),
      _ref_sgd(w32, g.astype(np.float32), lr, wd)),
  rtol=2e-3, atol=2e-3, **OPTIM_NO_GRAD)
S("mp_sgd_mom_update",
  lambda r: [u(r, 3, 4).astype(np.float16), u(r, 3, 4).astype(np.float16),
             u(r, 3, 4), u(r, 3, 4)],
  params={"lr": 0.1, "momentum": 0.9},
  ref=lambda w, g, m, w32, lr, momentum: (
      lambda wm: (wm[0].astype(np.float16), wm[1], wm[0]))(
      _ref_sgd_mom(w32, g.astype(np.float32), m, lr, momentum)),
  rtol=2e-3, atol=2e-3, **OPTIM_NO_GRAD)
S("adam_update",
  lambda r: [u(r, 3, 4), u(r, 3, 4), u(r, 3, 4), pos(r, 3, 4)],
  params={"lr": 0.01, "wd": 0.01}, ref=_ref_adam, **OPTIM_NO_GRAD)
S("rmsprop_update", lambda r: [u(r, 3, 4), u(r, 3, 4), pos(r, 3, 4)],
  params={"lr": 0.01}, ref=_ref_rmsprop, **OPTIM_NO_GRAD)
S("rmspropalex_update",
  lambda r: [u(r, 3, 4), u(r, 3, 4), pos(r, 3, 4, lo=1.0, hi=2.0),
             u(r, 3, 4, lo=-0.3, hi=0.3), u(r, 3, 4)],
  params={"lr": 0.01},
  ref=lambda w, g, n, gs, d, lr, gamma1=0.95, gamma2=0.9, epsilon=1e-8:
      (lambda n2, g2: (lambda d2: (w + d2, n2, g2, d2))(
          gamma2 * d - lr * g / np.sqrt(n2 - g2 ** 2 + epsilon)))(
      (1 - 0.95) * g ** 2 + 0.95 * n, (1 - 0.95) * g + 0.95 * gs),
  **OPTIM_NO_GRAD)
S("ftml_update",
  lambda r: [u(r, 3, 4), u(r, 3, 4), pos(r, 3, 4), pos(r, 3, 4),
             u(r, 3, 4)],
  params={"lr": 0.01, "t": 2},
  ref=lambda w, g, d, v, z, lr, t, beta1=0.6, beta2=0.999, epsilon=1e-8:
      (lambda v2: (lambda dt: (lambda z2: (-z2 / dt, dt, v2, z2))(
          beta1 * z + (1 - beta1) * g - (dt - beta1 * d) * w))(
          (1 - beta1 ** t) / lr * (np.sqrt(v2 / (1 - beta2 ** t)) + epsilon)))(
      beta2 * v + (1 - beta2) * g ** 2),
  **OPTIM_NO_GRAD)
S("signsgd_update", lambda r: [u(r, 3, 4), away0(r, 3, 4)],
  params={"lr": 0.1},
  ref=lambda w, g, lr: w - lr * np.sign(g), **OPTIM_NO_GRAD)
S("signum_update", lambda r: [u(r, 3, 4), away0(r, 3, 4), u(r, 3, 4)],
  params={"lr": 0.1, "momentum": 0.9},
  ref=lambda w, g, m, lr, momentum: (
      lambda m2: (w + lr * np.sign(m2), m2))(
      momentum * m - (1 - momentum) * g),
  **OPTIM_NO_GRAD)
S("ftrl_update",
  lambda r: [u(r, 3, 4), u(r, 3, 4), u(r, 3, 4), pos(r, 3, 4)],
  params={"lr": 0.1},
  ref=lambda w, g, z, n, lr, lamda1=0.01, beta=1.0, wd=0.0:
      (lambda n2: (lambda z2: (
          np.where(np.abs(z2) > lamda1,
                   -(z2 - np.sign(z2) * lamda1) /
                   ((beta + np.sqrt(n2)) / lr + wd),
                   np.zeros_like(w)), z2, n2))(
          z + g - (np.sqrt(n2) - np.sqrt(n)) / lr * w))(n + g ** 2),
  **OPTIM_NO_GRAD)
S("adagrad_update", lambda r: [u(r, 3, 4), u(r, 3, 4), pos(r, 3, 4)],
  params={"lr": 0.1}, ref=lambda w, g, h, lr: _ref_adagrad(w, g, h, lr),
  **OPTIM_NO_GRAD)

# ---- contrib / misc -------------------------------------------------------

S("_contrib_quadratic", lambda r: [u(r, 3, 4)],
  params={"a": 2.0, "b": -1.0, "c": 0.5},
  ref=lambda x, a, b, c: a * x ** 2 + b * x + c)
S("_contrib_quantize",
  lambda r: [u(r, 3, 4, lo=-0.9, hi=0.9), np.array([-1.0], np.float32),
             np.array([1.0], np.float32)],
  params={"out_type": "uint8"},
  ref=lambda d, lo, hi, out_type: (
      np.clip(np.round((d - lo[0]) * 255.0 / (hi[0] - lo[0])), 0,
              255).astype(np.uint8),
      lo, hi))
S("_contrib_dequantize",
  lambda r: [r.randint(0, 256, (3, 4)).astype(np.uint8),
             np.array([-1.0], np.float32), np.array([1.0], np.float32)],
  ref=lambda q, lo, hi: (q.astype(np.float32) * (hi[0] - lo[0]) / 255.0
                         + lo[0]),
  rtol=1e-3, atol=1e-3)
S("_contrib_fft", lambda r: [u(r, 2, 8)],
  ref=lambda x: np.stack([np.fft.fft(x).real, np.fft.fft(x).imag],
                         axis=-1).reshape(2, 16).astype(np.float32),
  rtol=1e-3, atol=1e-4)
S("_contrib_ifft", lambda r: [u(r, 2, 16)],
  ref=lambda x: (np.fft.ifft(
      x.reshape(2, 8, 2)[..., 0] + 1j * x.reshape(2, 8, 2)[..., 1]) *
      8).real.astype(np.float32),
  rtol=1e-3, atol=1e-4)
S("_contrib_count_sketch",
  lambda r: [u(r, 2, 5), np.array([0, 2, 1, 0, 3], np.float32),
             np.array([1, -1, 1, -1, 1], np.float32)],
  params={"out_dim": 4},
  ref=lambda d, h, s, out_dim: _np_count_sketch(d, h, s, out_dim),
  grad_args=[0])
S("_image_to_tensor", lambda r: [r.randint(0, 256, (5, 4, 3))
                                 .astype(np.uint8)],
  ref=lambda x: (x.astype(np.float32) / 255.0).transpose(2, 0, 1))
S("_image_normalize", lambda r: [u(r, 3, 4, 5, lo=0, hi=1)],
  params={"mean": (0.5, 0.4, 0.3), "std": (0.2, 0.25, 0.3)},
  ref=lambda x, mean, std: (x - np.array(mean).reshape(3, 1, 1)) /
      np.array(std).reshape(3, 1, 1))


def _np_count_sketch(d, h, s, out_dim):
    out = np.zeros((d.shape[0], out_dim), np.float32)
    for j in range(d.shape[1]):
        out[:, int(h[j])] += s[j] * d[:, j]
    return out


def _np_seq_reverse(d, sl):
    out = d.copy()
    for b in range(d.shape[1]):
        n = int(sl[b])
        out[:n, b] = d[:n, b][::-1]
    return out


# --------------------------------------------------------------------------
# the tests
# --------------------------------------------------------------------------


def test_registry_fully_covered():
    """The SPEC/SKIP partition is total over canonical registry ops."""
    names = set(_canonical_ops())
    covered = set(SPECS) | set(SKIP)
    missing = sorted(names - covered)
    stale = sorted(covered - names)
    assert not missing, "ops with neither spec nor skip reason: %s" % missing
    assert not stale, "specs for unregistered ops: %s" % stale
    overlap = sorted(set(SPECS) & set(SKIP))
    assert not overlap, "ops both specced and skipped: %s" % overlap


@pytest.mark.parametrize("name", sorted(SPECS))
def test_forward(name):
    spec = SPECS[name]
    r = np.random.RandomState(_seed(name))
    args = spec.args(r)
    outs = _run(name, args, spec.params)
    for o in outs:
        if np.asarray(o).dtype.kind == "f":
            assert np.all(np.isfinite(o)), "%s produced non-finite output" % name
    if spec.ref is not None:
        exp = spec.ref(*[a for a in args], **spec.params)
        exp = list(exp) if isinstance(exp, (tuple, list)) else [exp]
        assert len(outs) >= len(exp), \
            "%s: %d outputs < %d expected" % (name, len(outs), len(exp))
        for i, (o, e) in enumerate(zip(outs, exp)):
            np.testing.assert_allclose(
                np.asarray(o, np.float64), np.asarray(e, np.float64),
                rtol=spec.rtol, atol=spec.atol,
                err_msg="%s output %d" % (name, i))
    if spec.check is not None:
        spec.check(outs, args)


@pytest.mark.parametrize("name", sorted(SPECS))
def test_gradient(name):
    spec = SPECS[name]
    op = _canonical_ops()[name]
    if not op.differentiable:
        pytest.skip("op flagged non-differentiable")
    if spec.grad is False:
        assert spec.reason, "%s: grad disabled without a reason" % name
        pytest.skip(spec.reason)
    r = np.random.RandomState(_seed(name) + 1)
    args = spec.args(r)
    grad_idx = (spec.grad_args if spec.grad_args is not None
                else _float_arg_indices(args))
    if not grad_idx:
        pytest.skip("no float array inputs to differentiate")
    params = spec.params

    nd_args = [_to_nd(a) for a in args]
    for i in grad_idx:
        nd_args[i].attach_grad()
    with ag.record():
        out = getattr(nd, name)(*nd_args, **params)
    outs = out if isinstance(out, (list, tuple)) else [out]
    fouts = [o for o in outs if o.asnumpy().dtype.kind == "f"]
    assert fouts, "%s has no float outputs to project" % name
    projs = [r.normal(0, 1, o.shape).astype(np.float32) for o in fouts]
    ag.backward(fouts, head_grads=[nd.array(p) for p in projs])
    analytic = {i: nd_args[i].grad.asnumpy().astype(np.float64)
                for i in grad_idx}

    def f(mod):
        nds = [_to_nd(a) for a in mod]
        with ag.record():  # train-mode semantics, matching the analytic pass
            o = getattr(nd, name)(*nds, **params)
        os_ = o if isinstance(o, (list, tuple)) else [o]
        fs = [x for x in os_ if x.asnumpy().dtype.kind == "f"]
        return sum(float((x.asnumpy().astype(np.float64) * p).sum())
                   for x, p in zip(fs, projs))

    for i in grad_idx:
        base = args[i].astype(np.float64)
        flat_n = base.size
        if flat_n <= GRAD_COORD_CAP:
            coords = range(flat_n)
        else:
            coords = r.choice(flat_n, GRAD_COORD_CAP, replace=False)
        ana_flat = analytic[i].reshape(-1)
        for j in coords:
            pert = base.reshape(-1).copy()
            pert[j] += FD_EPS
            args_p = list(args)
            args_p[i] = pert.reshape(base.shape).astype(np.float32)
            fp = f(args_p)
            pert[j] -= 2 * FD_EPS
            args_m = list(args)
            args_m[i] = pert.reshape(base.shape).astype(np.float32)
            fm = f(args_m)
            gnum = (fp - fm) / (2 * FD_EPS)
            gana = ana_flat[j]
            assert abs(gana - gnum) <= spec.g_atol + spec.g_rtol * max(
                abs(gnum), abs(gana)), (
                "%s: d/d(arg%d)[%d] analytic %g vs numeric %g"
                % (name, i, j, gana, gnum))


# --------------------------------------------------------------------------
# explicit semantics tests backing SKIP/no-FD reasons above
# --------------------------------------------------------------------------


def test_blockgrad_blocks_gradient():
    x = nd.array(np.ones((3,), np.float32))
    x.attach_grad()
    with ag.record():
        y = (nd.BlockGrad(x) * nd.array(np.full((3,), 2.0, np.float32))
             + x).sum()
    y.backward()
    # only the direct `+ x` path contributes
    np.testing.assert_allclose(x.grad.asnumpy(), np.ones(3))


def test_output_head_gradients():
    """The custom_vjp loss heads produce the reference's training grads
    (src/operator/softmax_output-inl.h, regression_output-inl.h)."""
    r = np.random.RandomState(0)
    x = r.uniform(-1, 1, (3, 4)).astype(np.float32)
    lab = np.array([1, 3, 0], np.float32)

    xd = nd.array(x)
    xd.attach_grad()
    with ag.record():
        out = nd.SoftmaxOutput(xd, nd.array(lab))
    out.backward()
    sm = np_softmax(x, -1)
    onehot = np.eye(4, dtype=np.float32)[lab.astype(np.int64)]
    np.testing.assert_allclose(xd.grad.asnumpy(), sm - onehot,
                               rtol=1e-4, atol=1e-5)

    y = r.uniform(-1, 1, (3, 4)).astype(np.float32)
    xd = nd.array(x)
    xd.attach_grad()
    with ag.record():
        out = nd.LinearRegressionOutput(xd, nd.array(y))
    out.backward()
    np.testing.assert_allclose(xd.grad.asnumpy(), (x - y) / 4.0,
                               rtol=1e-4, atol=1e-5)


# --------------------------------------------------------------------------
# symbolic tier: replay every spec through Symbol + the jitted Executor
# --------------------------------------------------------------------------

# ops whose generic symbolic replay cannot work, with reasons
SYM_SKIP = {
    "_index": "getitem key params contain slice objects, which the "
              "symbol json/param path treats as internal (covered via "
              "NDArray.__getitem__ under autograd in test_autograd)",
    "_ones": "no array inputs: creation ops are frontend functions "
             "symbolically (sym.zeros/ones build constant nodes)",
    "_zeros": "see _ones",
    "BlockGrad": "covered by test_blockgrad_blocks_gradient",
}


def _sym_differs(name):
    """Ops where eval-mode executor output legitimately differs from the
    eager call (training-mode stochasticity is off in the executor)."""
    op = _canonical_ops()[name]
    return op.stateful


@pytest.mark.parametrize("name", sorted(SPECS))
def test_symbolic_forward(name):
    """Each spec replayed through sym.<op> + simple_bind matches the eager
    result — covering the symbolic arg mapping and the jitted Executor
    for the whole registry (reference test_operator.py exercises ops
    through simple_bind the same way)."""
    import mxtpu as mx
    import mxtpu.symbol as sym

    if name in SYM_SKIP:
        pytest.skip(SYM_SKIP[name])
    if _sym_differs(name):
        pytest.skip("stateful op: executor draws its own PRNG key")
    spec = SPECS[name]
    r = np.random.RandomState(_seed(name))
    args = spec.args(r)
    if not any(isinstance(a, np.ndarray) for a in args):
        pytest.skip("no array inputs")
    eager = _run(name, args, spec.params)

    op = _canonical_ops()[name]
    aux_pos = set(op.aux_update.keys())
    var_names = ["in%d" % i for i in range(len(args))]
    sym_fn = getattr(sym, name)
    sym_args = [sym.var(n) for n in var_names]
    out = sym_fn(*sym_args, **spec.params)
    arg_feed, aux_feed = {}, {}
    for i, (vn, a) in enumerate(zip(var_names, args)):
        (aux_feed if i in aux_pos else arg_feed)[vn] = nd.array(a)
    # auto-created inputs (implicit bias/label vars): zeros of the
    # inferred shape, matching their eager absence
    missing = [n_ for n_ in out.list_arguments() if n_ not in arg_feed]
    if missing:
        shapes, _, _ = out.infer_shape_partial(
            **{k: v.shape for k, v in arg_feed.items()})
        for n_, s in zip(out.list_arguments(), shapes):
            if n_ in missing:
                assert s is not None, "cannot infer %s for %s" % (n_, name)
                arg_feed[n_] = nd.zeros(s)
    ex = out.bind(mx.cpu(), arg_feed, aux_states=aux_feed or None)
    outs = [o.asnumpy() for o in ex.forward(is_train=False)]
    for i, (e, s) in enumerate(zip(eager, outs)):
        if np.asarray(e).dtype.kind == "f":
            np.testing.assert_allclose(
                np.asarray(s, np.float64), np.asarray(e, np.float64),
                rtol=1e-4, atol=1e-5,
                err_msg="%s symbolic output %d" % (name, i))
        else:
            np.testing.assert_array_equal(s, e)


@pytest.mark.parametrize("name", sorted(SPECS))
def test_symbolic_gradient(name):
    """The executor's fused forward+vjp produces the same input gradients
    as the eager tape for every differentiable op — locking the two
    autograd paths (per-op jax.vjp on the tape vs whole-graph jax.vjp in
    Executor._fwd_bwd) together."""
    import mxtpu as mx
    import mxtpu.symbol as sym

    if name in SYM_SKIP:
        pytest.skip(SYM_SKIP[name])
    op = _canonical_ops()[name]
    if not op.differentiable or _sym_differs(name):
        pytest.skip("non-differentiable or stateful")
    spec = SPECS[name]
    if spec.grad is False and spec.reason != NO_FD_CUSTOM_GRAD:
        # custom_vjp heads still compare eager-vs-symbolic (same vjp);
        # everything else skipped for grad has structural reasons
        pytest.skip(spec.reason)
    r = np.random.RandomState(_seed(name) + 7)
    args = spec.args(r)
    grad_idx = (spec.grad_args if spec.grad_args is not None
                else _float_arg_indices(args))
    if not grad_idx:
        pytest.skip("no float array inputs")
    params = spec.params

    # eager tape gradients
    nd_args = [_to_nd(a) for a in args]
    for i in grad_idx:
        nd_args[i].attach_grad()
    with ag.record():
        out = getattr(nd, name)(*nd_args, **params)
    outs = out if isinstance(out, (list, tuple)) else [out]
    fmask = [o.asnumpy().dtype.kind == "f" for o in outs]
    projs = [r.normal(0, 1, o.shape).astype(np.float32) if f else None
             for o, f in zip(outs, fmask)]
    ag.backward([o for o, f in zip(outs, fmask) if f],
                head_grads=[nd.array(p) for p in projs if p is not None])
    eager_grads = {i: nd_args[i].grad.asnumpy() for i in grad_idx}

    # symbolic executor gradients
    op_def = _canonical_ops()[name]
    aux_pos = set(op_def.aux_update.keys())
    var_names = ["in%d" % i for i in range(len(args))]
    s_out = getattr(sym, name)(*[sym.var(n) for n in var_names], **params)
    arg_feed, aux_feed = {}, {}
    for i, (vn, a) in enumerate(zip(var_names, args)):
        (aux_feed if i in aux_pos else arg_feed)[vn] = nd.array(a)
    missing = [n_ for n_ in s_out.list_arguments() if n_ not in arg_feed]
    if missing:
        shapes, _, _ = s_out.infer_shape_partial(
            **{k: v.shape for k, v in arg_feed.items()})
        for n_, sh in zip(s_out.list_arguments(), shapes):
            if n_ in missing:
                arg_feed[n_] = nd.zeros(sh)
    grad_names = {"in%d" % i for i in grad_idx}
    req = {n_: ("write" if n_ in grad_names else "null")
           for n_ in s_out.list_arguments()}
    ex = s_out.simple_bind(ctx=mx.cpu(), grad_req=req,
                           **{k: v.shape for k, v in arg_feed.items()})
    for k, v in arg_feed.items():
        ex.arg_dict[k]._assign_value(v)
    for k, v in aux_feed.items():
        ex.aux_dict[k]._assign_value(v)
    ex.forward(is_train=True)
    ex.backward([nd.array(p) if p is not None else
                 nd.zeros(o.shape)
                 for p, o in zip(projs, ex.outputs)])
    for i in grad_idx:
        np.testing.assert_allclose(
            ex.grad_dict["in%d" % i].asnumpy(), eager_grads[i],
            rtol=1e-4, atol=1e-5,
            err_msg="%s d/d(arg%d): executor vs tape" % (name, i))
