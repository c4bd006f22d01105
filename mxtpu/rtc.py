"""Runtime kernel compilation (reference ``python/mxnet/rtc.py``, 230 LoC).

The reference's ``CudaModule`` NVRTC-compiles CUDA-C source at runtime and
launches kernels on NDArrays by signature. The TPU-native rendering is
``PallasModule``: the source is *Python* defining Pallas kernel bodies
(functions of memory refs), compiled on first launch through
``pl.pallas_call`` → Mosaic on TPU (or the Pallas interpreter elsewhere).
The launch surface is kept shape-compatible with the reference:

    mod = mx.rtc.PallasModule(r'''
    def axpy(x_ref, y_ref, out_ref, *, alpha):
        out_ref[:] = alpha * x_ref[:] + y_ref[:]
    ''', exports=["axpy"])
    k = mod.get_kernel("axpy", "const float *x, const float *y, float *out")
    k.launch((x, y, out), mx.tpu(0), (1, 1, 1))     # grid like the reference

Signature rules (same grammar as reference rtc.py:get_kernel):
``const T *name`` = input tensor, ``T *name`` = output tensor, plain
``T name`` = scalar forwarded as a keyword argument to the kernel body.
Outputs take their shape/dtype from the NDArrays passed at launch.
"""
from __future__ import annotations

import re

import numpy as _np

from .ndarray import NDArray, _wrap

__all__ = ["PallasModule", "CudaModule"]

_DTYPES = {
    "float": _np.float32, "double": _np.float64, "__half": _np.float16,
    "half": _np.float16, "uint8_t": _np.uint8, "int": _np.int32,
    "int32_t": _np.int32, "int8_t": _np.int8, "char": _np.int8,
    "int64_t": _np.int64,
}


class _Param:
    __slots__ = ("name", "dtype", "is_ndarray", "is_const")

    def __init__(self, name, dtype, is_ndarray, is_const):
        self.name = name
        self.dtype = dtype
        self.is_ndarray = is_ndarray
        self.is_const = is_const


def _parse_signature(signature):
    params = []
    for tok in signature.split(","):
        tok = tok.strip()
        if not tok:
            continue
        is_const = False
        if tok.startswith("const "):
            is_const = True
            tok = tok[len("const "):].strip()
        is_ptr = "*" in tok
        tok = tok.replace("*", " ")
        parts = tok.split()
        if len(parts) != 2:
            raise ValueError("invalid function prototype: %r (expect "
                             "'[const] type [*] name')" % tok)
        tname, name = parts
        if tname not in _DTYPES:
            raise ValueError("unknown type %r in signature (supported: %s)"
                             % (tname, sorted(_DTYPES)))
        params.append(_Param(name, _DTYPES[tname], is_ptr, is_const))
    return params


class PallasModule:
    """Compile Pallas kernel bodies from source at runtime."""

    def __init__(self, source, options=(), exports=()):
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu
        # the source executes in a namespace pre-loaded with the kernel
        # vocabulary, mirroring how NVRTC sources assume the CUDA headers
        ns = {"jax": jax, "jnp": jnp, "pl": pl, "pltpu": pltpu,
              "np": _np}
        exec(compile(source, "<rtc>", "exec"), ns)
        self._ns = ns
        import inspect
        defined = [k for k, v in ns.items() if inspect.isfunction(v)
                   and v.__code__.co_filename == "<rtc>"]
        self._exports = list(exports) if exports else defined
        for name in self._exports:
            if name not in defined:
                raise ValueError("exported kernel %r not defined in source"
                                 % name)

    def get_kernel(self, name, signature):
        if name not in self._exports:
            raise ValueError("kernel %r not found (exports: %s)"
                             % (name, self._exports))
        return PallasKernel(self._ns[name], name, _parse_signature(signature))


class PallasKernel:
    """A launchable kernel (reference rtc.py:CudaKernel)."""

    def __init__(self, fn, name, params):
        self._fn = fn
        self._name = name
        self._params = params
        self._cache = {}   # (grid, scalars, shapes, dtypes) -> pallas_call

    def launch(self, args, ctx=None, grid_dims=(1, 1, 1),
               block_dims=None, shared_mem=0):
        """Run on the given NDArray/scalar args. ``grid_dims`` maps to the
        Pallas grid (trailing 1s dropped); ``block_dims``/``shared_mem``
        have no TPU meaning (Mosaic owns tiling) and are accepted for
        reference signature compatibility."""
        import functools
        import jax
        from jax.experimental import pallas as pl

        if len(args) != len(self._params):
            raise ValueError("kernel %s expects %d args, got %d"
                             % (self._name, len(self._params), len(args)))
        in_arrays, out_arrays, scalars = [], [], {}
        for a, p in zip(args, self._params):
            if p.is_ndarray:
                if not isinstance(a, NDArray):
                    raise TypeError("arg %r must be NDArray" % p.name)
                data = a._data.astype(p.dtype)
                (in_arrays if p.is_const else out_arrays).append((a, data))
            else:
                scalars[p.name] = p.dtype(a)
        gd = [int(g) for g in grid_dims]
        while gd and gd[-1] == 1:     # only TRAILING 1s are inert —
            gd.pop()                  # dropping interior 1s would renumber
        grid = tuple(gd)              # pl.program_id axes
        fn, tensor_params = self._fn, [p for p in self._params
                                       if p.is_ndarray]
        n_in = len(in_arrays)
        # FLOAT scalars ride as traced (1,)-operands so per-step values
        # (decaying epsilon) reuse one compile; INT scalars stay static
        # Python constants — kernels use them for loop bounds / shapes /
        # indexing, which tracers cannot serve — and key the cache.
        import numpy as _onp
        traced = {k: v for k, v in scalars.items()
                  if not _onp.issubdtype(type(v), _onp.integer)}
        static = {k: v for k, v in scalars.items() if k not in traced}
        traced_names = tuple(sorted(traced))
        n_scal = len(traced_names)
        key = (grid, traced_names, tuple(sorted(static.items())),
               tuple((d.shape, str(d.dtype)) for _, d in in_arrays),
               tuple((d.shape, str(d.dtype)) for _, d in out_arrays))
        call = self._cache.get(key)
        if call is None:
            def shim(*refs):
                # pallas ref order: tensor inputs, scalar inputs, outputs;
                # replay tensor refs in declared signature order so
                # 'float *out, const float *x' kernels see (out_ref,
                # x_ref) like the reference CudaKernel
                ins = list(refs[:n_in])
                kw = dict(static)
                kw.update({nme: refs[n_in + i][0]
                           for i, nme in enumerate(traced_names)})
                outs = list(refs[n_in + n_scal:])
                ordered = [(ins if p.is_const else outs).pop(0)
                           for p in tensor_params]
                return fn(*ordered, **kw)

            from .ops.pallas_util import per_platform
            call = jax.jit(functools.partial(per_platform, functools.partial(
                pl.pallas_call,
                shim,
                grid=grid,
                out_shape=[jax.ShapeDtypeStruct(d.shape, d.dtype)
                           for _, d in out_arrays],
            )))
            self._cache[key] = call
        import jax.numpy as jnp
        svals = [jnp.asarray(traced[nme]).reshape(1)
                 for nme in traced_names]
        outs = call(*([d for _, d in in_arrays] + svals))
        if not isinstance(outs, (list, tuple)):
            outs = [outs]
        for (arr, _), o in zip(out_arrays, outs):
            arr._data = o.astype(arr._data.dtype)
        return [arr for arr, _ in out_arrays]


# The reference class name: source language differs (Pallas-Python, not
# CUDA-C) but the object protocol (module -> get_kernel -> launch) is the
# same, so scripts porting from the reference only swap kernel bodies.
CudaModule = PallasModule
