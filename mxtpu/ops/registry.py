"""Operator registry.

Capability parity with MXNet's NNVM op registry (reference:
``include/mxnet/op_attr_types.h:183-250``, ``src/operator/``,
~181 ``NNVM_REGISTER_OP`` sites) re-designed TPU-first:

* An op is ONE pure JAX function ``fn(*arrays, **params) -> array | tuple``.
  There is no FCompute<cpu>/FCompute<gpu> twin-kernel split — XLA lowers the
  same trace to every backend, and Pallas kernels slot in as implementations
  of individual ops where stock XLA lowering is not enough.
* Shape/type inference (MXNet's InferShape/InferType passes,
  ``src/executor/infer_graph_attr_pass.cc``) is free via ``jax.eval_shape``
  on the same function — no per-op shape functions to maintain.
* Gradients (MXNet's FGradient) come from ``jax.vjp`` of the same function;
  ops that are not differentiable are flagged so the tape treats them as
  constants.

The same registry backs both the imperative ``nd.*`` namespace and the
symbolic ``sym.*`` namespace, mirroring how MXNet generates both frontends
from one registry (``python/mxnet/ndarray/register.py:29-168``).
"""
from __future__ import annotations

import threading

import jax

__all__ = ["OpDef", "register", "get_op", "list_ops", "alias",
           "next_rng_key", "rng_scope", "set_global_seed"]

_REGISTRY = {}


class OpDef:
    """A registered operator.

    Attributes
    ----------
    name : canonical op name (MXNet-compatible where one exists)
    fn : pure function of jax arrays + static keyword params
    differentiable : False for integer/index-valued ops (argmax, topk, ...)
    stateful : True if the op draws randomness via next_rng_key()
    """

    __slots__ = ("name", "fn", "differentiable", "stateful", "num_outputs",
                 "doc", "aux_update", "needs_train_flag", "user_outputs")

    def __init__(self, name, fn, differentiable=True, stateful=False,
                 num_outputs=1, doc=None, aux_update=None,
                 needs_train_flag=False, user_outputs=None):
        self.name = name
        self.fn = fn
        self.differentiable = differentiable
        self.stateful = stateful
        self.num_outputs = num_outputs
        self.doc = doc or fn.__doc__
        # aux_update: {input_index: output_index} — output j is the new value
        # of (mutable aux) input i; the eager layer writes it back in place,
        # the symbolic executor carries it as an aux-state update. This is the
        # functional rendering of MXNet's in-place aux_states (BatchNorm
        # moving_mean/var; see src/operator/nn/batch_norm.cc).
        self.aux_update = aux_update or {}
        # needs_train_flag: op fn accepts `_training=bool` injected from the
        # autograd/executor train-mode scope (MXNet ctx.is_train).
        self.needs_train_flag = needs_train_flag
        # user_outputs: how many leading outputs the frontend hands back to
        # the user (rest are aux updates / saved stats).
        self.user_outputs = user_outputs

    def __repr__(self):
        return "OpDef(%s)" % self.name


def register(name=None, differentiable=True, stateful=False, num_outputs=1,
             aliases=(), aux_update=None, needs_train_flag=False,
             user_outputs=None):
    """Decorator registering a pure-jax function as a framework op."""
    def deco(fn):
        opname = name or fn.__name__
        op = OpDef(opname, fn, differentiable=differentiable,
                   stateful=stateful, num_outputs=num_outputs,
                   aux_update=aux_update, needs_train_flag=needs_train_flag,
                   user_outputs=user_outputs)
        _REGISTRY[opname] = op
        for a in aliases:
            _REGISTRY[a] = op
        return fn
    return deco


def alias(existing, *names):
    op = _REGISTRY[existing]
    for n in names:
        _REGISTRY[n] = op


def get_op(name):
    return _REGISTRY.get(name)


def list_ops():
    return sorted(_REGISTRY)


# ---------------------------------------------------------------------------
# RNG plumbing.
#
# MXNet keeps per-device PRNG resources handed to ops via ResourceRequest
# (include/mxnet/resource.h:38-66). The functional JAX equivalent: stateful
# ops call ``next_rng_key()``. Eagerly that splits a global seed; inside a
# symbolic trace the executor pushes a *traced* key so randomness becomes an
# explicit input of the compiled XLA computation (fresh key each step).
# ---------------------------------------------------------------------------

class _RngState(threading.local):
    def __init__(self):
        self.key = jax.random.PRNGKey(0)
        self.stack = []  # holders pushed by tracers


_RNG = _RngState()


def set_global_seed(seed):
    _RNG.key = jax.random.PRNGKey(seed)
    _RNG.stack = list(_RNG.stack)  # keep any active trace holders


class _KeyHolder:
    __slots__ = ("key", "used")

    def __init__(self, key):
        self.key = key
        self.used = False


class rng_scope:
    """Context manager a tracer uses to supply a (traced) base key."""

    def __init__(self, key):
        self.holder = _KeyHolder(key)

    def __enter__(self):
        _RNG.stack.append(self.holder)
        return self.holder

    def __exit__(self, *a):
        _RNG.stack.pop()


def split2(key):
    """jax.random.split without the host sync: ``a, b = split2(k)``.

    NEVER tuple-unpack a concrete split result (``a, b =
    jax.random.split(k)``): iterating a jax.Array goes through
    Array.__iter__, which materializes chunks on the HOST — a full
    async-queue drain per call, which serializes every hybridized
    forward. Indexing
    yields lazy device slices and keeps the dispatch async. (Unpacking
    a *tracer* inside jit is fine — but using this helper everywhere
    keeps the eager paths safe by habit.)"""
    ks = jax.random.split(key)
    return ks[0], ks[1]


def next_rng_key():
    """Return a fresh PRNG key (eager: global state; traced: from scope)."""
    if _RNG.stack:
        holder = _RNG.stack[-1]
        holder.key, sub = split2(holder.key)
        holder.used = True
        return sub
    _RNG.key, sub = split2(_RNG.key)
    return sub
