"""Generate typed C++ Symbol wrappers for every registered operator.

Reference counterpart: cpp-package/OpWrapperGenerator.py — there it parses
the C API's op signatures (MXSymbolGetAtomicSymbolInfo) and emits op.h; here
we introspect the Python registry directly (the registry is the single
source of truth for both frontends) and emit include/mxtpu-cpp/op.hpp.

Usage: python tools/gen_cpp_op_wrappers.py  (rewrites op.hpp in place)
"""
from __future__ import annotations

import inspect
import keyword
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# Optional *array* inputs (default None in the op fn but an NDArray/Symbol
# input, not a static param). Everything else defaulting to None is a param.
OPT_INPUTS = {
    "bias", "gamma", "beta", "moving_mean", "moving_var", "sequence_length",
    "state_cell", "crop_like", "trans", "grid", "label", "weight32",
    "data_lengths", "label_lengths", "valid_len", "q_gain", "k_gain", "load",
}

# C++ reserved words that appear as op names or arg names
RESERVED = {"float", "double", "int", "bool", "operator", "new", "delete",
            "default", "template", "register", "union"}


def cpp_ident(name):
    if name in RESERVED or keyword.iskeyword(name):
        return name + "_"
    return name


def cpp_op_name(name):
    """Op name -> C++ function name (strip leading underscores of private
    namespaces; the reference capitalizes similarly in op.h)."""
    out = name.lstrip("_")
    out = out.replace(".", "_")
    return cpp_ident(out)


def param_decl(pname, default):
    """Map a python default value to a (c++ type, default literal) pair.

    All params cross the ABI as dmlc-style strings; typed C++ arguments are
    formatted by fmt_expr below.
    """
    pname = cpp_ident(pname)
    if isinstance(default, bool):
        return "bool", "true" if default else "false"
    if isinstance(default, int):
        return "int", str(default)
    if isinstance(default, float):
        v = repr(default)
        return "double", v
    if isinstance(default, str):
        return "const std::string &", '"%s"' % default
    if isinstance(default, tuple):
        return "Tuple", "Tuple{%s}" % ", ".join(repr(float(x))
                                                for x in default)
    if default is None:
        # stringly-typed escape hatch; "None" means "leave at op default"
        return "const std::string &", '"None"'
    raise TypeError("unmapped default %r for %s" % (default, pname))


def fmt_expr(pname, ctype):
    pname = cpp_ident(pname)
    if ctype == "bool":
        return '(%s ? "true" : "false")' % pname
    if ctype == "Tuple":
        return "TupleStr(%s)" % pname
    if ctype.startswith("const std::string"):
        return pname
    if ctype == "double":
        # std::to_string fixes 6 decimal places: to_string(1e-7) is
        # "0.000000", which would silently zero a scalar operand
        # (e.g. op::mul_scalar's multiplier). NumStr round-trips.
        return "NumStr(%s)" % pname
    return "std::to_string(%s)" % pname


def gen_op(name, op):
    try:
        sig = inspect.signature(op.fn)
    except (TypeError, ValueError):
        return None
    inputs, opt_inputs, params = [], [], []
    varargs = None
    for pname, p in sig.parameters.items():
        if pname.startswith("_"):
            continue
        if p.kind == inspect.Parameter.VAR_POSITIONAL:
            varargs = pname
        elif p.kind == inspect.Parameter.VAR_KEYWORD:
            continue
        elif p.default is inspect.Parameter.empty:
            inputs.append(pname)
        elif p.default is None and pname in OPT_INPUTS:
            opt_inputs.append(pname)
        else:
            params.append((pname, p.default))

    fn_name = cpp_op_name(name)
    args = ["const std::string &name"]
    if varargs:
        args.append("const std::vector<Symbol> &%s" % cpp_ident(varargs))
    args += ["Symbol %s" % cpp_ident(i) for i in inputs]
    body_params = []
    for pname, default in params:
        try:
            ctype, dflt = param_decl(pname, default)
        except TypeError:
            return None  # unmappable op: callers use Operator directly
        sep = " " if ctype.endswith("&") else " "
        args.append("%s%s%s = %s" % (ctype, sep, cpp_ident(pname), dflt))
        body_params.append((pname, ctype))
    args += ["Symbol %s = Symbol()" % cpp_ident(i) for i in opt_inputs]

    lines = []
    lines.append("inline Symbol %s(%s) {" % (fn_name, ",\n    ".join(args)))
    lines.append('  Operator op("%s");' % name)
    for pname, ctype in body_params:
        if ctype.startswith("const std::string"):
            # "None" sentinel: leave the op's own default in place
            lines.append('  if (%s != "None") op.SetParam("%s", %s);'
                         % (cpp_ident(pname), pname,
                            fmt_expr(pname, ctype)))
        else:
            lines.append('  op.SetParam("%s", %s);'
                         % (pname, fmt_expr(pname, ctype)))
    if varargs:
        lines.append("  for (const auto &s : %s) op.PushInput(s);"
                     % cpp_ident(varargs))
    for i in inputs:
        lines.append('  op.SetInput("%s", %s);' % (i, cpp_ident(i)))
    for i in opt_inputs:
        lines.append("  if (!%s.IsNull()) op.SetInput(\"%s\", %s);"
                     % (cpp_ident(i), i, cpp_ident(i)))
    lines.append("  return op.CreateSymbol(name);")
    lines.append("}")
    return "\n".join(lines)


def main():
    import jax
    jax.config.update("jax_platforms", "cpu")
    from mxtpu.ops import registry

    seen = {}
    for n in registry.list_ops():
        op = registry.get_op(n)
        seen.setdefault(op.name, op)

    out = []
    out.append("""\
/* GENERATED by tools/gen_cpp_op_wrappers.py — do not edit by hand.
 *
 * Typed Symbol-building wrappers for every registered operator, generated
 * from the op registry the same way the reference's OpWrapperGenerator.py
 * generates cpp-package/include/mxnet-cpp/op.h from its C API. Ops whose
 * signatures cannot be typed (var-keyword params) are reachable through
 * the generic Operator class instead.
 */
#ifndef MXTPU_CPP_OP_HPP_
#define MXTPU_CPP_OP_HPP_

#include <string>
#include <vector>

#include "base.hpp"
#include "operator.hpp"
#include "symbol.hpp"

namespace mxtpu {
namespace cpp {
namespace op {
""")
    skipped = []
    for name in sorted(seen):
        code = gen_op(name, seen[name])
        if code is None:
            skipped.append(name)
            continue
        out.append(code)
        out.append("")
    out.append("}  // namespace op")
    out.append("}  // namespace cpp")
    out.append("}  // namespace mxtpu")
    out.append("")
    out.append("#endif  // MXTPU_CPP_OP_HPP_")
    dest = os.path.join(os.path.dirname(__file__), "..", "include",
                        "mxtpu-cpp", "op.hpp")
    with open(dest, "w") as f:
        f.write("\n".join(out))
    print("wrote %s: %d wrappers, %d skipped (%s)"
          % (dest, len(seen) - len(skipped), len(skipped),
             ", ".join(skipped)))


if __name__ == "__main__":
    main()
