"""Symbolic graph layer.

Capability parity with NNVM's ``Symbol/Graph`` (external submodule in the
reference, consumed via ``python/mxnet/symbol/symbol.py``, 2,848 LoC) —
re-designed for XLA: a Symbol is a lightweight DAG over registered ops;
"binding" it traces the whole graph (forward and backward) into ONE jitted
XLA computation. MXNet's PlanMemory / bulk-exec / PlaceDevice passes are
subsumed by the XLA compiler; InferShape/InferType run via ``jax.eval_shape``
over the same trace plus per-op parameter-shape hints.
"""
from __future__ import annotations

import inspect
import json

import numpy as _np
import jax
import jax.numpy as jnp

from ..attribute import current as _attr_scope_current
from ..base import canonical_dtype
from ..context import current_context
from ..ops.registry import get_op, rng_scope
from .. import name as _name_mgr

__all__ = ["Symbol", "var", "Variable", "Group", "load", "load_json", "zeros",
           "ones"]


class _Node:
    """Graph node: an op application or a free variable."""

    __slots__ = ("op", "name", "inputs", "params", "num_outputs", "attrs",
                 "aux_positions", "input_names")

    def __init__(self, op, name, inputs=(), params=None, attrs=None,
                 input_names=()):
        self.op = op                    # OpDef or None for variables
        self.name = name
        self.inputs = list(inputs)      # list of (node, out_index)
        self.params = dict(params or {})
        self.attrs = dict(attrs or {})
        self.input_names = list(input_names)
        self.num_outputs = 1
        self.aux_positions = set(op.aux_update.keys()) if op else set()

    @property
    def is_variable(self):
        return self.op is None


class Symbol:
    """An (ordered) set of outputs of a graph — same surface as mx.sym.Symbol."""

    def __init__(self, outputs):
        self._outputs = list(outputs)   # list of (node, out_index)

    # -- composition helpers ----------------------------------------------
    @property
    def name(self):
        if len(self._outputs) == 1:
            return self._outputs[0][0].name
        return None

    def __repr__(self):
        return "<Symbol %s>" % (self.name or "group",)

    def __getitem__(self, index):
        if isinstance(index, str):
            names = self.list_outputs()
            index = names.index(index)
        return Symbol([self._outputs[index]])

    def __len__(self):
        return len(self._outputs)

    def __iter__(self):
        for i in range(len(self._outputs)):
            yield self[i]

    def get_internals(self):
        """Symbol exposing every internal node output, like sym.get_internals()."""
        outs = []
        for node in self._topo():
            for i in range(node.num_outputs):
                outs.append((node, i))
        return Symbol(outs)

    def get_children(self):
        node = self._outputs[0][0]
        if not node.inputs:
            return None
        return Symbol(list(node.inputs))

    # -- graph traversal ---------------------------------------------------
    def _topo(self):
        seen = set()
        order = []

        def visit(node):
            if id(node) in seen:
                return
            seen.add(id(node))
            for (n, _) in node.inputs:
                visit(n)
            order.append(node)

        for (n, _) in self._outputs:
            visit(n)
        return order

    def _classify_vars(self):
        """Return (arg_nodes, aux_nodes) in first-visit order."""
        aux_ids = set()
        arg_ids = set()
        order = []
        for node in self._topo():
            if node.is_variable and "__scalar__" not in node.attrs:
                order.append(node)
        for node in self._topo():
            if node.op is None:
                continue
            for pos, (inp, _) in enumerate(node.inputs):
                if inp.is_variable:
                    if pos in node.aux_positions:
                        aux_ids.add(id(inp))
                    else:
                        arg_ids.add(id(inp))
        args, auxs = [], []
        for v in order:
            if id(v) in aux_ids and id(v) not in arg_ids:
                auxs.append(v)
            else:
                args.append(v)
        return args, auxs

    def list_arguments(self):
        return [n.name for n in self._classify_vars()[0]]

    def list_auxiliary_states(self):
        return [n.name for n in self._classify_vars()[1]]

    def list_outputs(self):
        names = []
        for (node, idx) in self._outputs:
            if node.num_outputs == 1:
                names.append(node.name + "_output")
            else:
                names.append("%s_output%d" % (node.name, idx))
        return names

    def list_inputs(self):
        return self.list_arguments() + self.list_auxiliary_states()

    # -- attributes --------------------------------------------------------
    def list_attr(self, recursive=False):
        """Attributes of this symbol's output node (reference
        symbol.py:list_attr); attr_dict() for the whole graph."""
        if recursive:
            return self.attr_dict()
        return dict(self._outputs[0][0].attrs)

    def attr(self, key):
        return self._outputs[0][0].attrs.get(key)

    def attr_dict(self):
        out = {}
        for node in self._topo():
            if node.attrs:
                out[node.name] = dict(node.attrs)
        return out

    def _set_attr(self, **kwargs):
        self._outputs[0][0].attrs.update(kwargs)

    # -- shape / type inference -------------------------------------------
    def infer_shape(self, *args, **kwargs):
        try:
            return self._infer_shape_impl(False, *args, **kwargs)
        except Exception:
            raise

    def infer_shape_partial(self, *args, **kwargs):
        return self._infer_shape_impl(True, *args, **kwargs)

    def _infer_shape_impl(self, partial, *args, **kwargs):
        arg_names = self.list_arguments()
        known = {}
        if args:
            for n, s in zip(arg_names, args):
                if s is not None:
                    known[n] = tuple(s)
        known.update({k: tuple(v) for k, v in kwargs.items() if v is not None})
        shapes, out_shapes, aux_shapes = _infer_graph_shapes(self, known, partial)
        arg_shapes = [shapes.get(n) for n in arg_names]
        aux_names = self.list_auxiliary_states()
        return (arg_shapes, out_shapes, [shapes.get(n) for n in aux_names])

    def infer_storage_type(self, *args, **kwargs):
        """Infer storage types ("default"/"csr"/"row_sparse") for all
        arguments, outputs and aux states (the reference's
        InferStorageType pass, src/executor/infer_graph_attr_pass.cc).

        Input stypes come from ``var(stype=...)`` declarations, overridden
        by positional (list_arguments order) or keyword stypes given here.
        Ops without a sparse rule produce "default" outputs — the dense
        fallback, which is free on the dense-backed TPU representation.
        """
        from .storage_type import infer_graph_storage_types
        arg_names = self.list_arguments()
        known = {}
        if args:
            for n, s in zip(arg_names, args):
                if s is not None:
                    known[n] = s
        known.update({k: v for k, v in kwargs.items() if v is not None})
        var_stypes, out_stypes = infer_graph_storage_types(self, known)
        arg_stypes = [var_stypes.get(n, "default") for n in arg_names]
        aux_stypes = [var_stypes.get(n, "default")
                      for n in self.list_auxiliary_states()]
        return arg_stypes, out_stypes, aux_stypes

    def infer_type(self, *args, **kwargs):
        arg_names = self.list_arguments()
        dtypes = {}
        if args:
            for n, t in zip(arg_names, args):
                if t is not None:
                    dtypes[n] = canonical_dtype(t)
        dtypes.update({k: canonical_dtype(v) for k, v in kwargs.items()})
        default = _np.dtype(_np.float32)
        arg_types = [dtypes.get(n, default) for n in arg_names]
        aux_types = [default for _ in self.list_auxiliary_states()]
        out_types = [default for _ in self._outputs]
        return arg_types, out_types, aux_types

    # -- arithmetic --------------------------------------------------------
    def _binop(self, opname, other, reverse=False):
        if isinstance(other, Symbol):
            a, b = (other, self) if reverse else (self, other)
            return _apply_op(get_op(opname), None, [a, b], {})
        # scalar: fold into graph as a scalar param via a lambda-free path
        a = self
        scalar = float(other)
        const = _ScalarConst(scalar)
        pair = (const, a) if reverse else (a, const)
        return _apply_op(get_op(opname), None, list(pair), {})

    def __add__(self, o): return self._binop("broadcast_add", o)
    def __radd__(self, o): return self._binop("broadcast_add", o, True)
    def __sub__(self, o): return self._binop("broadcast_sub", o)
    def __rsub__(self, o): return self._binop("broadcast_sub", o, True)
    def __mul__(self, o): return self._binop("broadcast_mul", o)
    def __rmul__(self, o): return self._binop("broadcast_mul", o, True)
    def __truediv__(self, o): return self._binop("broadcast_div", o)
    def __rtruediv__(self, o): return self._binop("broadcast_div", o, True)
    def __pow__(self, o): return self._binop("broadcast_power", o)
    def __neg__(self): return _apply_op(get_op("negative"), None, [self], {})

    def __getattr__(self, name):
        if name.startswith("_"):
            raise AttributeError(name)
        op = get_op(name)
        if op is None:
            raise AttributeError(name)

        def method(*args, **kwargs):
            return _create_symbol(op, *( (self,) + args ), **kwargs)
        return method

    # -- serialization -----------------------------------------------------
    def tojson(self):
        """Graph JSON (same role as nnvm's save-json; custom schema)."""
        nodes = self._topo()
        idx = {id(n): i for i, n in enumerate(nodes)}
        jnodes = []
        for n in nodes:
            jn = {
                "op": n.op.name if n.op else "null",
                "name": n.name,
                "attrs": {k: repr(v) for k, v in n.params.items()},
                "inputs": [[idx[id(i)], oi] for (i, oi) in n.inputs],
            }
            if n.input_names:
                jn["input_names"] = list(n.input_names)
            if n.is_variable and n.attrs:
                # persist scalar consts / declared shapes / hints
                va = {}
                for k, v in n.attrs.items():
                    if k == "__dtype__":
                        va[k] = _np.dtype(v).name
                    elif k != "__init__":
                        va[k] = repr(v) if not isinstance(v, str) else v
                jn["var_attrs"] = va
            jnodes.append(jn)
        heads = [[idx[id(n)], oi] for (n, oi) in self._outputs]
        return json.dumps({"nodes": jnodes, "heads": heads,
                           "mxtpu_version": 1}, indent=2)

    def save(self, fname):
        with open(fname, "w") as f:
            f.write(self.tojson())

    # -- binding -----------------------------------------------------------
    def simple_bind(self, ctx=None, grad_req="write", type_dict=None,
                    stype_dict=None, **kwargs):
        from ..executor import Executor
        return Executor._simple_bind(self, ctx or current_context(),
                                     grad_req, type_dict, kwargs,
                                     stype_dict=stype_dict)

    def bind(self, ctx, args, args_grad=None, grad_req="write",
             aux_states=None, group2ctx=None, shared_exec=None):
        from ..executor import Executor
        return Executor._bind(self, ctx, args, args_grad, grad_req, aux_states)

    def eval(self, ctx=None, **kwargs):
        ex = self.bind(ctx or current_context(), kwargs)
        return ex.forward()

    # grad of all outputs wrt args (parity: sym.grad not widely used)
    def grad(self, wrt):
        raise NotImplementedError("use simple_bind + backward")


class _ScalarConst:
    """Marker wrapped into the graph for sym <op> scalar expressions."""

    def __init__(self, value):
        self.value = value


# ---------------------------------------------------------------------------
# Symbol creation from ops
# ---------------------------------------------------------------------------

# Optional (default=None) fn parameters that denote *array* inputs; any other
# default-None parameter (axes=None, a_min=None, ...) is a static param.
_OPTIONAL_ARRAY_PARAMS = {"bias", "gamma", "state", "state_cell", "weight32",
                          "parameters", "crop_like", "trans",
                          "sequence_length", "data_lengths",
                          "label_lengths", "valid_len", "q_gain", "k_gain",
                          "load"}

# optional array inputs that are genuinely absent when not supplied — no
# implicit variable is auto-created for them (unlike bias/state, which are
# real parameters the frontend materializes)
_OPTIONAL_NO_AUTO = {"crop_like", "trans", "sequence_length",
                     "data_lengths", "label_lengths", "valid_len", "q_gain",
                     "k_gain", "load"}


def _array_input_names(op, params):
    """Leading fn parameters that are array inputs."""
    try:
        sig = inspect.signature(op.fn)
    except (TypeError, ValueError):
        return []
    names = []
    for p in sig.parameters.values():
        if p.kind == inspect.Parameter.VAR_POSITIONAL:
            return None  # variadic
        if p.kind == inspect.Parameter.VAR_KEYWORD:
            break        # **kwargs holds passthrough params, not inputs
        if p.default is inspect.Parameter.empty:
            if p.name.startswith("_"):
                continue
            names.append(p.name)
        elif p.default is None and p.name in _OPTIONAL_ARRAY_PARAMS:
            names.append(p.name)
        else:
            break
    # op-specific trims
    if op.name in ("Convolution", "Deconvolution", "FullyConnected",
                   "_contrib_DeformableConvolution"):
        # honor each op's own no_bias default (Deconvolution defaults to
        # bias-less, Convolution/FullyConnected to biased)
        default_no_bias = sig.parameters["no_bias"].default \
            if "no_bias" in sig.parameters else False
        if params.get("no_bias", default_no_bias):
            names = [n for n in names if n != "bias"]
    if op.name == "LeakyReLU" and params.get("act_type", "leaky") != "prelu":
        names = [n for n in names if n != "gamma"]
    return names


def _create_symbol(op, *args, **kwargs):
    name = kwargs.pop("name", None)
    attrs = kwargs.pop("attr", None)
    attrs = _attr_scope_current().get(attrs)   # with AttrScope(...): stamping
    # split symbol inputs passed as kwargs
    sym_kwargs = {k: v for k, v in kwargs.items() if isinstance(v, Symbol)}
    for k in sym_kwargs:
        kwargs.pop(k)
    params = kwargs
    name = _name_mgr.current().get(name, op.name.lower().split("_")[-1]
                                   if op.name.islower() else op.name.lower())
    input_names = _array_input_names(op, params)
    inputs = []
    used_names = []
    if input_names is None:
        # variadic op: positional symbols only
        inputs = list(args)
        used_names = ["arg%d" % i for i in range(len(inputs))]
    else:
        # A None positional means "this slot not supplied" (gluon passes
        # op(x, weight, None, no_bias=True)) — it must consume its slot so
        # later symbols don't shift into earlier inputs.
        pos = list(args)
        for i, argname in enumerate(input_names):
            supplied = None
            if pos:
                supplied = pos.pop(0)
            if supplied is not None and not isinstance(supplied, Symbol):
                # a concrete (non-Symbol) value for an input-classified
                # name is a static parameter: sym.tile(x, reps=(2,2)) and
                # sym.sgd_update(w, g, 0.1) — required fn args without
                # defaults look like inputs to the signature heuristic.
                # Arrays are NOT params: an nd/sym mix-up must fail loudly.
                from ..ndarray import NDArray as _NDArray
                if isinstance(supplied, (_NDArray, _np.ndarray)):
                    raise TypeError(
                        "op %s input %r must be a Symbol, got %s (mixing "
                        "NDArrays into symbol construction?)"
                        % (op.name, argname, type(supplied).__name__))
                if argname in params:
                    raise TypeError(
                        "op %s got multiple values for argument %r"
                        % (op.name, argname))
                params[argname] = supplied
                continue
            if supplied is None and argname in params:
                continue                    # static param given by keyword
            if supplied is not None:
                inputs.append(supplied)
                used_names.append(argname)
            elif argname in sym_kwargs:
                inputs.append(sym_kwargs.pop(argname))
                used_names.append(argname)
            elif argname in _OPTIONAL_NO_AUTO:
                continue            # genuinely optional: fn gets None
            elif argname == "state_cell" and \
                    params.get("mode", "lstm") != "lstm":
                # only LSTM has a cell state; auto-creating a variable for
                # GRU/vanilla RNN would surface a bogus learnable arg
                continue
            else:
                # auto-create variable (MXNet: implicit weight/bias/label vars)
                suffix = argname
                if op.name in ("SoftmaxOutput", "LinearRegressionOutput",
                               "LogisticRegressionOutput",
                               "MAERegressionOutput", "SVMOutput") \
                        and argname == "label":
                    vname = name + "_label"
                else:
                    vname = "%s_%s" % (name, suffix)
                inputs.append(var(vname))
                used_names.append(argname)
        if sym_kwargs:
            raise TypeError("unexpected symbol kwargs %s for op %s"
                            % (list(sym_kwargs), op.name))
        pos = [a for a in pos if a is not None]   # leftover Nones are
        if pos:                                    # legitimately unsupplied
            raise TypeError(
                "op %s consumes %d array inputs (%s) but got %d "
                "positional symbols — extra inputs would be silently "
                "dropped; pass optional array inputs by keyword or add "
                "them to _OPTIONAL_ARRAY_PARAMS"
                % (op.name, len(input_names), input_names, len(args)))
    return _apply_op(op, name, inputs, params, attrs, used_names)


def _apply_op(op, name, inputs, params, attrs=None, input_names=()):
    in_refs = []
    for s in inputs:
        if isinstance(s, Symbol):
            if len(s._outputs) != 1:
                raise ValueError("cannot use grouped symbol as op input")
            in_refs.append(s._outputs[0])
        elif isinstance(s, _ScalarConst):
            n = _Node(None, "_scalar_%r" % s.value)
            n.attrs["__scalar__"] = s.value
            in_refs.append((n, 0))
        else:
            raise TypeError("op inputs must be Symbols, got %r" % (s,))
    if name is None:
        name = _name_mgr.current().get(None, op.name.lower())
    node = _Node(op, name, in_refs, params, attrs, input_names)
    node.num_outputs = _node_num_outputs(op, params)
    nuser = op.user_outputs
    if callable(nuser):
        nuser = nuser(params)
    nuser = nuser or node.num_outputs
    return Symbol([(node, i) for i in range(nuser)])


def _node_num_outputs(op, params):
    """Output arity of an op node, including param-dependent cases
    (single source of truth for _apply_op and load_json)."""
    n = op.num_outputs if isinstance(op.num_outputs, int) else 1
    if op.name in ("split", "SliceChannel"):
        return int(params.get("num_outputs", 2))
    if op.name == "topk":
        return 2 if params.get("ret_typ") == "both" else 1
    if op.name == "sample_multinomial":
        return 2 if params.get("get_prob") else 1
    if op.name in ("_contrib_Proposal", "_contrib_MultiProposal"):
        return 2 if params.get("output_score") else 1
    if op.name == "RNN":
        return 1 if not params.get("state_outputs") else \
            (3 if params.get("mode", "lstm") == "lstm" else 2)
    if op.name == "Custom":
        from ..operator import custom_num_outputs
        return custom_num_outputs(params)
    return n


def var(name, attr=None, shape=None, lr_mult=None, wd_mult=None, dtype=None,
        init=None, stype=None, **kwargs):
    """Create a free variable (parity with sym.var / sym.Variable)."""
    node = _Node(None, name)
    attr = _attr_scope_current().get(attr)   # AttrScope stamps vars too
    if attr:
        node.attrs.update(attr)
    if shape is not None:
        node.attrs["__shape__"] = tuple(shape)
    if dtype is not None:
        node.attrs["__dtype__"] = canonical_dtype(dtype)
    if lr_mult is not None:
        node.attrs["__lr_mult__"] = lr_mult
    if wd_mult is not None:
        node.attrs["__wd_mult__"] = wd_mult
    if init is not None:
        # accept Initializer instances or their dumps() JSON string
        node.attrs["__init__"] = init if isinstance(init, str) \
            else init.dumps()
    if stype is not None:
        node.attrs["__stype__"] = stype
    node.attrs.update(kwargs)
    return Symbol([(node, 0)])


Variable = var


def Group(symbols):
    outs = []
    for s in symbols:
        outs.extend(s._outputs)
    return Symbol(outs)


def load(fname):
    with open(fname) as f:
        return load_json(f.read())


def load_json(json_str):
    d = json.loads(json_str)
    nodes = []
    for jn in d["nodes"]:
        if jn["op"] == "null":
            node = _Node(None, jn["name"])
            for k, v in jn.get("var_attrs", {}).items():
                if k == "__dtype__":
                    node.attrs[k] = _np.dtype(v)
                elif k == "__stype__":
                    node.attrs[k] = v  # plain string, not a python literal
                elif isinstance(v, str) and k.startswith("__"):
                    node.attrs[k] = eval(v, {"__builtins__": {}}, {})  # noqa: S307
                else:
                    node.attrs[k] = v
        else:
            op = get_op(jn["op"])
            if op is None:
                raise ValueError("unknown op %r in symbol json" % jn["op"])
            params = {k: eval(v, {"__builtins__": {}}, {})  # noqa: S307
                      for k, v in jn.get("attrs", {}).items()}
            node = _Node(op, jn["name"], params=params,
                         input_names=jn.get("input_names", ()))
        nodes.append(node)
    for node, jn in zip(nodes, d["nodes"]):
        node.inputs = [(nodes[i], oi) for (i, oi) in jn["inputs"]]
        if node.op:
            node.aux_positions = set(node.op.aux_update.keys())
            node.num_outputs = _node_num_outputs(node.op, node.params)
    return Symbol([(nodes[i], oi) for (i, oi) in d["heads"]])


# ---------------------------------------------------------------------------
# Graph evaluation (shared by Executor and shape inference)
# ---------------------------------------------------------------------------

def _build_consumer_map(nodes):
    consumers = {}
    for n in nodes:
        for (inp, _oi) in n.inputs:
            consumers.setdefault(id(inp), []).append(n)
    return consumers


def _creation_batch(node, consumers, get_input_shape, fallback_shapes):
    """Resolve the MXNet 'unknown batch' (dim 0 in a _zeros/_ones shape).

    Preferred: an RNN consumer pins it — fused states are (L*D, N, H) and
    RNN data is TNC, so batch = data_shape[1] (both subtrees precede the
    state in DFS order, so the data shape is already available). Fallback:
    the leading dim of a bound variable named 'data'/'*_data', else the
    first known variable shape.
    """
    for c in consumers.get(id(node), ()):
        if c.op is not None and c.op.name == "RNN" and c.inputs:
            s = get_input_shape(c.inputs[0])
            if s is not None and len(s) >= 2:
                return s[1]
    for name, s in fallback_shapes.items():
        if (name == "data" or name.endswith("_data")) and len(s) > 0:
            return s[0]
    return next((s[0] for s in fallback_shapes.values() if len(s) > 0),
                None)


def eval_graph(sym_outputs, feed, training=False):
    """Evaluate graph outputs given {var_name: jax value}.

    Returns (outputs, aux_updates) where aux_updates maps aux var name →
    new value (functional rendering of MXNet's in-place aux mutation).
    """
    cache = {}
    aux_updates = {}
    consumer_map = _build_consumer_map(Symbol(list(sym_outputs))._topo())

    def eval_node(node):
        key = id(node)
        if key in cache:
            return cache[key]
        if node.is_variable:
            if "__scalar__" in node.attrs:
                vals = (node.attrs["__scalar__"],)
            else:
                if node.name not in feed:
                    raise KeyError("no value bound for variable %r" % node.name)
                vals = (feed[node.name],)
        else:
            in_vals = []
            for (inp, oi) in node.inputs:
                in_vals.append(eval_node(inp)[oi])
            params = dict(node.params)
            if node.op.needs_train_flag:
                params["_training"] = training
            if node.op.name in ("_zeros", "_ones") \
                    and 0 in tuple(params.get("shape", ())):
                # MXNet convention: dim 0 in a state/creation shape means
                # "unknown batch"
                def _in_shape(ref):
                    n2, oi2 = ref
                    vals2 = eval_node(n2)
                    v2 = vals2[oi2]
                    return tuple(getattr(v2, "shape", ())) or None
                fb = {k: tuple(v.shape) for k, v in feed.items()
                      if getattr(v, "ndim", 0) > 0}
                batch = _creation_batch(node, consumer_map, _in_shape, fb)
                if batch:
                    params["shape"] = tuple(batch if d == 0 else d
                                            for d in params["shape"])
            # every HLO operation of a compiled step carries the
            # operator and node it came from in its op_name, forward and
            # transposed: what a device trace's `fusion` is made of
            with jax.named_scope("%s/%s" % (node.op.name, node.name)):
                if _OPTIONAL_NO_AUTO.intersection(node.input_names):
                    # an optional input left out ahead of one that is
                    # given would shift the positions: bind by name
                    out = node.op.fn(**dict(zip(node.input_names, in_vals)),
                                     **params)
                else:
                    out = node.op.fn(*in_vals, **params)
            vals = out if isinstance(out, tuple) else (out,)
            for in_pos, out_idx in node.op.aux_update.items():
                if in_pos < len(node.inputs):
                    src, _ = node.inputs[in_pos]
                    if src.is_variable:
                        aux_updates[src.name] = vals[out_idx]
        cache[key] = vals
        return vals

    outputs = [eval_node(n)[oi] for (n, oi) in sym_outputs]
    return outputs, aux_updates


# ---------------------------------------------------------------------------
# Shape inference: forward walk with per-op parameter-shape hints.
# ---------------------------------------------------------------------------

_SHAPE_HINTS = {}


def shape_hint(opname):
    def deco(fn):
        _SHAPE_HINTS[opname] = fn
        return fn
    return deco


@shape_hint("FullyConnected")
def _fc_hint(params, in_shapes, input_names):
    data = in_shapes.get("data")
    if data is None:
        return {}
    nh = int(params.get("num_hidden", 0))
    if params.get("flatten", True):
        d = 1
        for s in data[1:]:
            d *= s
    else:
        d = data[-1]
    out = {"weight": (nh, d)}
    if "bias" in input_names:
        out["bias"] = (nh,)
    return out


@shape_hint("Convolution")
def _conv_hint(params, in_shapes, input_names):
    data = in_shapes.get("data")
    if data is None:
        return {}
    nf = int(params.get("num_filter", 0))
    ng = int(params.get("num_group", 1))
    kernel = tuple(params.get("kernel", ()))
    out = {"weight": (nf, data[1] // ng) + kernel}
    if "bias" in input_names:
        out["bias"] = (nf,)
    return out


@shape_hint("Deconvolution")
def _deconv_hint(params, in_shapes, input_names):
    data = in_shapes.get("data")
    if data is None:
        return {}
    nf = int(params.get("num_filter", 0))
    ng = int(params.get("num_group", 1))
    kernel = tuple(params.get("kernel", ()))
    out = {"weight": (data[1], nf // ng) + kernel}
    if "bias" in input_names:
        out["bias"] = (nf,)
    return out


@shape_hint("BatchNorm")
def _bn_hint(params, in_shapes, input_names):
    data = in_shapes.get("data")
    if data is None:
        return {}
    axis = int(params.get("axis", 1)) % len(data)
    c = (data[axis],)
    return {"gamma": c, "beta": c, "moving_mean": c, "moving_var": c}


@shape_hint("LayerNorm")
def _ln_hint(params, in_shapes, input_names):
    data = in_shapes.get("data")
    if data is None:
        return {}
    axis = int(params.get("axis", -1)) % len(data)
    c = (data[axis],)
    return {"gamma": c, "beta": c}


@shape_hint("RMSNorm")
def _rms_hint(params, in_shapes, input_names):
    data = in_shapes.get("data")
    if data is None:
        return {}
    return {"gamma": (data[int(params.get("axis", -1)) % len(data)],)}


@shape_hint("InstanceNorm")
def _in_hint(params, in_shapes, input_names):
    data = in_shapes.get("data")
    if data is None:
        return {}
    return {"gamma": (data[1],), "beta": (data[1],)}


@shape_hint("Embedding")
def _emb_hint(params, in_shapes, input_names):
    return {"weight": (int(params["input_dim"]), int(params["output_dim"]))}


@shape_hint("LeakyReLU")
def _lrelu_hint(params, in_shapes, input_names):
    data = in_shapes.get("data")
    if data is None or params.get("act_type") != "prelu":
        return {}
    return {"gamma": (data[1],)}


def _label_hint(params, in_shapes, input_names):
    data = in_shapes.get("data")
    if data is None:
        return {}
    if params.get("multi_output"):
        return {"label": (data[0],) + tuple(data[2:])}
    return {"label": (data[0],)}


for _n in ("SoftmaxOutput", "SVMOutput"):
    _SHAPE_HINTS[_n] = _label_hint


def _reg_label_hint(params, in_shapes, input_names):
    data = in_shapes.get("data")
    return {"label": data} if data else {}


for _n in ("LinearRegressionOutput", "LogisticRegressionOutput",
           "MAERegressionOutput"):
    _SHAPE_HINTS[_n] = _reg_label_hint


def _infer_graph_shapes(sym, known, partial=False):
    """Forward fixpoint: fill variable shapes via hints, then eval_shape."""
    shapes = dict(known)  # var name -> shape
    node_out_dtypes = {}
    nodes = sym._topo()
    consumer_map = _build_consumer_map(nodes)
    # include declared shapes on vars; dims of 0 mean "unknown" (MXNet's
    # deferred-init convention) so such shapes don't count as known
    for n in nodes:
        if n.is_variable and "__shape__" in n.attrs and n.name not in shapes:
            s = tuple(n.attrs["__shape__"])
            if all(d > 0 for d in s):
                shapes[n.name] = s

    node_out_shapes = {}

    def in_shape_map(node):
        m = {}
        for pos, (inp, oi) in enumerate(node.inputs):
            argname = node.input_names[pos] if pos < len(node.input_names) \
                else "arg%d" % pos
            if inp.is_variable:
                if "__scalar__" in inp.attrs:
                    m[argname] = ()
                elif inp.name in shapes:
                    m[argname] = shapes[inp.name]
            elif id(inp) in node_out_shapes:
                m[argname] = node_out_shapes[id(inp)][oi]
        return m

    for node in nodes:
        if node.is_variable:
            continue
        ism = in_shape_map(node)
        hint = _SHAPE_HINTS.get(node.op.name)
        if hint is not None:
            filled = hint(node.params, ism, node.input_names)
            for pos, (inp, oi) in enumerate(node.inputs):
                argname = node.input_names[pos] if pos < len(node.input_names) \
                    else None
                if inp.is_variable and argname in filled \
                        and inp.name not in shapes:
                    shapes[inp.name] = tuple(filled[argname])
        # try to eval_shape this node
        in_specs = []
        ok = True
        for pos, (inp, oi) in enumerate(node.inputs):
            if inp.is_variable:
                if "__scalar__" in inp.attrs:
                    in_specs.append(inp.attrs["__scalar__"])
                    continue
                if inp.name not in shapes:
                    ok = False
                    break
                dt = inp.attrs.get("__dtype__", _np.float32)
                in_specs.append(jax.ShapeDtypeStruct(shapes[inp.name],
                                                     canonical_dtype(dt)))
            else:
                if id(inp) not in node_out_shapes:
                    ok = False
                    break
                shp, dt = node_out_shapes[id(inp)][oi], \
                    node_out_dtypes[id(inp)][oi]
                in_specs.append(jax.ShapeDtypeStruct(shp, dt))
        if not ok:
            if partial:
                continue
            raise ValueError("cannot infer shapes for node %r: missing input "
                             "shapes" % node.name)
        params = dict(node.params)
        if node.op.needs_train_flag:
            params["_training"] = False
        if node.op.name in ("_zeros", "_ones") \
                and 0 in tuple(params.get("shape", ())):
            def _in_shape(ref):
                n2, oi2 = ref
                if n2.is_variable:
                    return shapes.get(n2.name)
                got = node_out_shapes.get(id(n2))
                return got[oi2] if got else None
            fb = {k: v for k, v in known.items()}
            batch = _creation_batch(node, consumer_map, _in_shape, fb)
            if batch:
                params["shape"] = tuple(batch if d == 0 else d
                                        for d in params["shape"])

        def f(*xs):
            r = node.op.fn(*xs, **params)
            return r if isinstance(r, tuple) else (r,)

        with rng_scope(jax.random.PRNGKey(0)):
            out = jax.eval_shape(f, *in_specs)
        node_out_shapes[id(node)] = [tuple(o.shape) for o in out]
        node_out_dtypes[id(node)] = [o.dtype for o in out]

    out_shapes = []
    for (n, oi) in sym._outputs:
        if n.is_variable:
            out_shapes.append(shapes.get(n.name))
        else:
            got = node_out_shapes.get(id(n))
            out_shapes.append(got[oi] if got else None)
    aux = {}
    return shapes, out_shapes, aux


def __getattr__(name):
    op = get_op(name)
    if op is None:
        raise AttributeError("module 'mxtpu.symbol' has no attribute %r" % name)

    def fn(*args, **kwargs):
        return _create_symbol(op, *args, **kwargs)
    fn.__name__ = name
    fn.__doc__ = op.doc
    return fn


def zeros(shape, dtype="float32", **kwargs):
    raise NotImplementedError("use a variable + executor feed instead")


def ones(shape, dtype="float32", **kwargs):
    raise NotImplementedError("use a variable + executor feed instead")


class _ContribNamespace:
    """``sym.contrib.X`` resolves registry op ``_contrib_X`` (or plain X),
    mirroring python/mxnet/symbol/contrib.py."""

    def __getattr__(self, name):
        for candidate in ("_contrib_" + name, name):
            op = get_op(candidate)
            if op is not None:
                def fn(*args, _op=op, **kwargs):
                    return _create_symbol(_op, *args, **kwargs)
                fn.__name__ = name
                return fn
        raise AttributeError("no contrib op %r" % name)


contrib = _ContribNamespace()


@shape_hint("RNN")
def _rnn_hint(params, in_shapes, input_names):
    data = in_shapes.get("data")
    if data is None:
        return {}
    mode = params.get("mode", "lstm")
    state_size = int(params.get("state_size", 0))
    num_layers = int(params.get("num_layers", 1))
    bidir = bool(params.get("bidirectional", False))
    dirs = 2 if bidir else 1
    from ..ops.rnn import rnn_param_size
    psize = rnn_param_size(mode, data[2], state_size, num_layers, bidir)
    out = {"parameters": (psize,),
           "state": (num_layers * dirs, data[1], state_size)}
    if "state_cell" in input_names:
        out["state_cell"] = (num_layers * dirs, data[1], state_size)
    return out
