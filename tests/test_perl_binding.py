"""Perl binding tier: build the AI::MXTpu XS module against libmxtpu_c.so
and run its test suite. Reference counterpart: perl-package/AI-MXNet tests.
Proves the core C ABI is consumable from a non-Python host runtime."""
import os
import shutil
import subprocess

import pytest

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
_PKG = os.path.join(_ROOT, "perl-package", "AI-MXTpu")
_NATIVE = os.path.join(_ROOT, "mxtpu", "_native")

# slow: toolchain (perl Makefile.PL + make build the XS module)
pytestmark = pytest.mark.slow


def test_perl_binding(tmp_path):
    if shutil.which("perl") is None:
        pytest.skip("no perl")
    probe = subprocess.run(["perl", "-MExtUtils::MakeMaker", "-e", "1"],
                           capture_output=True)
    if probe.returncode != 0:
        pytest.skip("no ExtUtils::MakeMaker")
    res = subprocess.run(["make", "-C", _NATIVE, "libmxtpu_c.so"],
                         capture_output=True, text=True)
    if res.returncode != 0:
        pytest.skip("libmxtpu_c.so build failed: " + res.stderr[-500:])
    env = dict(os.environ, MXTPU_ROOT=_ROOT, PYTHONPATH=_ROOT,
               JAX_PLATFORMS="cpu")
    subprocess.run(["perl", "Makefile.PL"], cwd=_PKG, env=env, check=True,
                   capture_output=True)
    subprocess.run(["make"], cwd=_PKG, env=env, check=True,
                   capture_output=True)
    res = subprocess.run(["perl", "t/01_basic.t"], cwd=_PKG, env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "ok 7" in res.stdout, res.stdout
