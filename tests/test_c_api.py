"""Core C ABI tier: build libmxtpu_c.so, compile the C test drivers, run
them. Reference counterpart: the reference's c_api is exercised through
binding test suites; here tests/cpp/c_api_test.cc drives it directly and
example/c_api/train_lenet.c proves end-to-end training through the ABI."""
import os
import shutil
import subprocess
import sys

import pytest

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
_NATIVE = os.path.join(_ROOT, "mxtpu", "_native")
_SO = os.path.join(_NATIVE, "libmxtpu_c.so")

# slow: toolchain (make + g++/gcc build libmxtpu_c.so and the C drivers)
pytestmark = pytest.mark.slow


def _build_so():
    if shutil.which("g++") is None:
        pytest.skip("no g++")
    res = subprocess.run(["make", "-C", _NATIVE, "libmxtpu_c.so"],
                         capture_output=True, text=True)
    if res.returncode != 0:
        pytest.skip("libmxtpu_c.so build failed: " + res.stderr[-500:])


def _run_c(tmp_path, src, exe_name, cc="g++", extra=(), args=()):
    _build_so()
    exe = str(tmp_path / exe_name)
    subprocess.run(
        [cc, "-O1", src, "-I", _ROOT, "-L", _NATIVE, "-lmxtpu_c",
         "-Wl,-rpath," + _NATIVE, "-o", exe] + list(extra),
        check=True)
    env = dict(os.environ, PYTHONPATH=_ROOT, JAX_PLATFORMS="cpu")
    return subprocess.run([exe] + list(args), capture_output=True,
                          text=True, timeout=600, env=env)


def test_c_api_unit(tmp_path):
    res = _run_c(tmp_path,
                 os.path.join(_ROOT, "tests", "cpp", "c_api_test.cc"),
                 "c_api_test", cc="g++", extra=["-std=c++17"],
                 args=[str(tmp_path)])
    assert res.returncode == 0, res.stdout + res.stderr
    assert "c_api_test OK" in res.stdout


def test_c_api_train_lenet(tmp_path):
    if shutil.which("gcc") is None:
        pytest.skip("no gcc")
    res = _run_c(tmp_path,
                 os.path.join(_ROOT, "example", "c_api", "train_lenet.c"),
                 "train_lenet", cc="gcc", extra=["-lm"])
    assert res.returncode == 0, res.stdout + res.stderr
    assert "train_lenet (C ABI) OK" in res.stdout
