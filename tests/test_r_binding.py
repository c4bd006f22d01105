"""Compile-only CI gate for the R binding.

This image has no R toolchain, so `R CMD SHLIB` cannot run; instead the
.Call glue is fully type-checked by gcc against a minimal stub of R's C
API (tests/cpp/r_stub/). The gate catches the failure classes that
matter without R installed: signature drift against include/mxtpu/
c_api.h, undeclared identifiers, and syntax errors. A real R build is
documented in R-package/src/mxtpu_r.c's header comment.
"""
import glob
import os
import shutil
import subprocess

import pytest

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

# slow: toolchain (gcc type-checks the R glue and builds a C host)
pytestmark = pytest.mark.slow


def test_r_glue_typechecks_against_c_abi():
    if shutil.which("gcc") is None:
        pytest.skip("no gcc")
    srcs = glob.glob(os.path.join(_ROOT, "R-package", "src", "*.c"))
    assert srcs, "R glue sources missing"
    res = subprocess.run(
        ["gcc", "-fsyntax-only", "-Wall", "-Werror",
         "-I", os.path.join(_ROOT, "tests", "cpp", "r_stub"),
         "-I", _ROOT] + srcs,
        capture_output=True, text=True)
    assert res.returncode == 0, res.stderr[-2000:]


def test_c_hosted_r_sequence(tmp_path):
    """Execute the R glue's exact C-ABI call sequence without R: the
    harness (R-package/src/smoke_harness.c) drives every MX* call each
    .Call wrapper makes, in wrapper order, against the real
    libmxtpu_c.so — including the symbol-JSON -> bind -> forward
    executor path on a symbol generated by the Python frontend. R's
    layer above these calls is SEXP marshalling only."""
    if shutil.which("gcc") is None:
        pytest.skip("no gcc")
    import sys
    native = os.path.join(_ROOT, "mxtpu", "_native")
    subprocess.run(["make", "-C", native, "libmxtpu_c.so"],
                   check=True, capture_output=True)
    # a real two-layer symbol for the executor leg, via the public API
    gen = (
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "import sys; sys.path.insert(0, %r)\n"
        "import mxtpu as mx\n"
        "s = mx.sym.Activation(mx.sym.broadcast_add(\n"
        "    mx.sym.var('x'), mx.sym.var('y')), act_type='tanh')\n"
        "s.save(%r)\n" % (_ROOT, str(tmp_path / "sym.json")))
    subprocess.run([sys.executable, "-c", gen], check=True,
                   capture_output=True, text=True,
                   env=dict(os.environ, JAX_PLATFORMS="cpu"))
    exe = str(tmp_path / "r_smoke")
    subprocess.run(
        ["gcc", "-O1", os.path.join(_ROOT, "R-package", "src",
                                    "smoke_harness.c"),
         "-I", _ROOT, "-L", native, "-lmxtpu_c",
         "-Wl,-rpath," + native, "-lm", "-o", exe],
        check=True, capture_output=True, text=True)
    out = subprocess.run(
        [exe, str(tmp_path / "sym.json")], capture_output=True,
        text=True, timeout=300,
        env=dict(os.environ, PYTHONPATH=_ROOT, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stdout + out.stderr
    assert "R_SEQUENCE_OK" in out.stdout, out.stdout
