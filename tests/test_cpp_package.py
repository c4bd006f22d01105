"""mxtpu-cpp training package tier: the generated op wrappers stay in sync
with the registry, and the C++ LeNet example compiles and converges.
Reference counterpart: cpp-package/tests + cpp-package/example/lenet.cpp."""
import os
import shutil
import subprocess
import sys

import pytest

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
_NATIVE = os.path.join(_ROOT, "mxtpu", "_native")

# slow: toolchain (g++ builds the C++ LeNet against libmxtpu_c.so; the
# wrapper test rewrites include/mxtpu-cpp/op.hpp in place)
pytestmark = pytest.mark.slow


def test_op_wrappers_up_to_date(tmp_path):
    """Regenerating op.hpp must reproduce the checked-in file, so a newly
    registered op cannot ship without its C++ wrapper."""
    checked_in = os.path.join(_ROOT, "include", "mxtpu-cpp", "op.hpp")
    with open(checked_in) as f:
        before = f.read()
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_ROOT)
    subprocess.run([sys.executable,
                    os.path.join(_ROOT, "tools", "gen_cpp_op_wrappers.py")],
                   check=True, env=env, capture_output=True)
    with open(checked_in) as f:
        after = f.read()
    assert before == after, ("include/mxtpu-cpp/op.hpp is stale; rerun "
                             "tools/gen_cpp_op_wrappers.py")


def test_cpp_train_lenet(tmp_path):
    if shutil.which("g++") is None:
        pytest.skip("no g++")
    res = subprocess.run(["make", "-C", _NATIVE, "libmxtpu_c.so"],
                         capture_output=True, text=True)
    if res.returncode != 0:
        pytest.skip("libmxtpu_c.so build failed: " + res.stderr[-500:])
    exe = str(tmp_path / "train_lenet_cpp")
    subprocess.run(
        ["g++", "-O1", "-std=c++14",
         os.path.join(_ROOT, "example", "cpp", "train_lenet.cpp"),
         "-I", os.path.join(_ROOT, "include"),
         "-L", _NATIVE, "-lmxtpu_c", "-Wl,-rpath," + _NATIVE,
         "-o", exe],
        check=True)
    env = dict(os.environ, PYTHONPATH=_ROOT, JAX_PLATFORMS="cpu")
    res = subprocess.run([exe], capture_output=True, text=True,
                         timeout=600, env=env)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "train_lenet (mxtpu-cpp) OK" in res.stdout
