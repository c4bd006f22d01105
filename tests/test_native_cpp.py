"""C++ unit-test tier (reference tests/cpp/ gtest suites): compile and run
the native recordio test against libmxtpu_io.so."""
import os
import shutil
import subprocess

import pytest

_ROOT = os.path.join(os.path.dirname(__file__), "..")
_NATIVE = os.path.join(_ROOT, "mxtpu", "_native")

# slow: toolchain (g++ builds the recordio test against libmxtpu_io.so)
pytestmark = pytest.mark.slow


def test_recordio_cpp(tmp_path):
    if shutil.which("g++") is None:
        pytest.skip("no g++")
    so = os.path.join(_NATIVE, "libmxtpu_io.so")
    if not os.path.exists(so):
        pytest.skip("libmxtpu_io.so not built")
    exe = str(tmp_path / "recordio_test")
    subprocess.run(
        ["g++", "-O1", "-std=c++17",
         os.path.join(_ROOT, "tests", "cpp", "recordio_test.cc"),
         "-L", _NATIVE, "-lmxtpu_io",
         "-Wl,-rpath," + os.path.abspath(_NATIVE), "-o", exe],
        check=True)
    res = subprocess.run([exe, str(tmp_path)], capture_output=True,
                         text=True, timeout=120)
    assert res.returncode == 0, res.stderr
    assert "recordio_test OK" in res.stdout
