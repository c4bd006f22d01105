"""C predict API tests: drive the flat C ABI (libmxtpu_predict.so) via
ctypes and via a freshly compiled pure-C program, comparing against the
Python Module.predict path (reference tests exercise c_predict_api through
the amalgamation/cpp-package).
"""
import ctypes
import os
import subprocess

import numpy as np
import pytest

import mxtpu as mx
from mxtpu import nd

# slow: toolchain (make + gcc/g++ build libmxtpu_predict.so, a C and a C++
# program and the amalgamation)
pytestmark = pytest.mark.slow

_NATIVE = os.path.join(os.path.dirname(__file__), "..", "mxtpu", "_native")
_SO = os.path.join(_NATIVE, "libmxtpu_predict.so")


def _export_model(tmp_path):
    mx.random.seed(0)
    data = mx.sym.var("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=3, name="fc2")
    out = mx.sym.SoftmaxOutput(fc2, name="softmax")
    mod = mx.mod.Module(out, context=mx.cpu())
    rng = np.random.RandomState(0)
    x = rng.randn(32, 5).astype(np.float32)
    y = (x.sum(1) > 0).astype(np.float32)
    mod.fit(mx.io.NDArrayIter(x, y, batch_size=8), num_epoch=1,
            optimizer="sgd", optimizer_params={"learning_rate": 0.1},
            initializer=mx.init.Xavier())
    prefix = str(tmp_path / "m")
    mod.save_checkpoint(prefix, 1)
    probe = np.arange(10, dtype=np.float32).reshape(2, 5) / 10.0
    sym2, arg, aux = mx.model.load_checkpoint(prefix, 1)
    mod2 = mx.mod.Module(out, context=mx.cpu())
    mod2.bind(data_shapes=[("data", (2, 5))], for_training=False)
    mod2.set_params(arg, aux)
    expect = mod2.predict(
        mx.io.NDArrayIter(probe, None, batch_size=2)).asnumpy()
    return prefix, probe, expect


@pytest.mark.skipif(not os.path.exists(_SO),
                    reason="libmxtpu_predict.so not built")
def test_c_predict_ctypes(tmp_path):
    prefix, probe, expect = _export_model(tmp_path)
    lib = ctypes.CDLL(_SO)
    lib.MXGetLastError.restype = ctypes.c_char_p
    json_data = open(prefix + "-symbol.json", "rb").read()
    params = open(prefix + "-0001.params", "rb").read()
    keys = (ctypes.c_char_p * 1)(b"data")
    indptr = (ctypes.c_uint * 2)(0, 2)
    shape = (ctypes.c_uint * 2)(2, 5)
    handle = ctypes.c_void_p()
    rc = lib.MXPredCreate(json_data, params, len(params), 1, 0, 1, keys,
                          indptr, shape, ctypes.byref(handle))
    assert rc == 0, lib.MXGetLastError()
    flat = probe.ravel().astype(np.float32)
    buf = (ctypes.c_float * flat.size)(*flat)
    assert lib.MXPredSetInput(handle, b"data", buf, flat.size) == 0
    assert lib.MXPredForward(handle) == 0
    sdata = ctypes.POINTER(ctypes.c_uint)()
    ndim = ctypes.c_uint()
    assert lib.MXPredGetOutputShape(handle, 0, ctypes.byref(sdata),
                                    ctypes.byref(ndim)) == 0
    oshape = tuple(sdata[i] for i in range(ndim.value))
    assert oshape == (2, 3)
    out = (ctypes.c_float * 6)()
    assert lib.MXPredGetOutput(handle, 0, out, 6) == 0
    got = np.asarray(out[:6], np.float32).reshape(2, 3)
    np.testing.assert_allclose(got, expect, rtol=1e-5, atol=1e-6)
    # reshape path: new batch size
    indptr2 = (ctypes.c_uint * 2)(0, 2)
    shape2 = (ctypes.c_uint * 2)(4, 5)
    h2 = ctypes.c_void_p()
    assert lib.MXPredReshape(1, keys, indptr2, shape2, handle,
                             ctypes.byref(h2)) == 0, lib.MXGetLastError()
    probe4 = np.tile(probe, (2, 1)).astype(np.float32)
    buf4 = (ctypes.c_float * 20)(*probe4.ravel())
    assert lib.MXPredSetInput(h2, b"data", buf4, 20) == 0
    assert lib.MXPredForward(h2) == 0
    out4 = (ctypes.c_float * 12)()
    assert lib.MXPredGetOutput(h2, 0, out4, 12) == 0
    got4 = np.asarray(out4[:12], np.float32).reshape(4, 3)
    np.testing.assert_allclose(got4[:2], expect, rtol=1e-5, atol=1e-6)
    lib.MXPredFree(handle)
    lib.MXPredFree(h2)


_C_PROGRAM = r"""
#include <stdio.h>
#include <stdlib.h>
#include "mxtpu/c_predict_api.h"
static char *rf(const char *p, long *n) {
  FILE *f = fopen(p, "rb"); fseek(f, 0, SEEK_END); *n = ftell(f);
  fseek(f, 0, SEEK_SET); char *b = malloc(*n + 1);
  fread(b, 1, *n, f); b[*n] = 0; fclose(f); return b;
}
int main(int argc, char **argv) {
  long js, ps;
  char *j = rf(argv[1], &js), *p = rf(argv[2], &ps);
  const char *keys[] = {"data"};
  mx_uint ip[] = {0, 2}, sh[] = {2, 5};
  PredictorHandle h = NULL;
  if (MXPredCreate(j, p, (int)ps, 1, 0, 1, keys, ip, sh, &h)) {
    fprintf(stderr, "%s\n", MXGetLastError()); return 1; }
  mx_float in[10];
  for (int i = 0; i < 10; ++i) in[i] = i / 10.0f;
  if (MXPredSetInput(h, "data", in, 10) || MXPredForward(h)) return 1;
  mx_float out[6];
  if (MXPredGetOutput(h, 0, out, 6)) return 1;
  for (int i = 0; i < 6; ++i) printf("%.6f ", out[i]);
  MXPredFree(h);
  return 0;
}
"""


@pytest.mark.skipif(not os.path.exists(_SO),
                    reason="libmxtpu_predict.so not built")
def test_c_predict_from_pure_c_program(tmp_path):
    import shutil
    if shutil.which("gcc") is None:
        pytest.skip("no gcc")
    prefix, probe, expect = _export_model(tmp_path)
    src = tmp_path / "t.c"
    src.write_text(_C_PROGRAM)
    exe = str(tmp_path / "t")
    inc = os.path.join(os.path.dirname(__file__), "..", "include")
    subprocess.run(["gcc", "-O1", str(src), "-I", inc, "-L", _NATIVE,
                    "-lmxtpu_predict", "-o", exe,
                    "-Wl,-rpath," + os.path.abspath(_NATIVE)], check=True)
    env = dict(os.environ,
               PYTHONPATH=os.path.abspath(
                   os.path.join(os.path.dirname(__file__), "..")),
               JAX_PLATFORMS="cpu")
    res = subprocess.run([exe, prefix + "-symbol.json",
                          prefix + "-0001.params"], env=env,
                         capture_output=True, text=True, timeout=240)
    assert res.returncode == 0, res.stderr
    got = np.asarray([float(v) for v in res.stdout.split()],
                     np.float32).reshape(2, 3)
    np.testing.assert_allclose(got, expect, rtol=1e-5, atol=1e-6)


def test_predict_impl_output_shape_before_forward(tmp_path):
    from mxtpu import _c_predict_impl as impl
    prefix, probe, expect = _export_model(tmp_path)
    json_data = open(prefix + "-symbol.json").read()
    params = open(prefix + "-0001.params", "rb").read()
    pred = impl.create(json_data, params, 1, 0, ["data"], [(2, 5)])
    # reference MXPredCreate infers output shapes at bind time; clients
    # size their buffers from this before ever calling forward
    assert pred.output_shape(0) == [2, 3]
    pred.set_input("data", probe.ravel())
    pred.forward()
    np.testing.assert_allclose(
        pred.output(0).reshape(2, 3), expect, rtol=1e-5, atol=1e-5)


def test_predict_impl_reshape_does_not_alias_inputs(tmp_path):
    from mxtpu import _c_predict_impl as impl
    prefix, probe, expect = _export_model(tmp_path)
    json_data = open(prefix + "-symbol.json").read()
    params = open(prefix + "-0001.params", "rb").read()
    pred = impl.create(json_data, params, 1, 0, ["data"], [(2, 5)])
    pred.set_input("data", probe.ravel())

    # same-shape reshape: inputs must be independent copies
    pred2 = impl.reshape(pred, ["data"], [(2, 5)])
    assert pred2.output_shape(0) == [2, 3]
    assert pred2._exe.arg_dict["data"] is not pred._exe.arg_dict["data"]
    # executor-internal views (arg_arrays) must agree with arg_dict
    for i, n in enumerate(pred2._exe._arg_names):
        assert pred2._exe.arg_arrays[i] is pred2._exe.arg_dict[n]
    pred2.set_input("data", np.zeros(10, np.float32))
    pred.forward()
    np.testing.assert_allclose(
        pred.output(0).reshape(2, 3), expect, rtol=1e-5, atol=1e-5)

    # weights stay shared semantically: new predictor still computes the
    # trained function on its own input
    pred2.set_input("data", probe.ravel())
    pred2.forward()
    np.testing.assert_allclose(
        pred2.output(0).reshape(2, 3), expect, rtol=1e-5, atol=1e-5)


_CPP_PROGRAM = r"""
#include <cstdio>
#include <mxtpu/mxtpu_cpp.hpp>

int main(int argc, char **argv) {
  using mxtpu::cpp::Predictor;
  using mxtpu::cpp::Context;
  Predictor pred(mxtpu::cpp::LoadFile(argv[1]),
                 mxtpu::cpp::LoadFile(argv[2]), Context::cpu(),
                 {{"data", {2, 5}}});
  std::vector<mx_uint> shape = pred.GetOutputShape(0);  // pre-forward
  if (shape.size() != 2 || shape[0] != 2 || shape[1] != 3) return 2;
  std::vector<mx_float> probe(10);
  for (int i = 0; i < 10; ++i) probe[i] = i / 10.0f;
  pred.SetInput("data", probe);
  pred.Forward();
  mxtpu::cpp::NDArray out = pred.GetOutputArray(0);
  // reshape keeps weights; run the same input through the new predictor
  Predictor pred2 = pred.Reshape({{"data", {2, 5}}});
  pred2.SetInput("data", probe);
  pred2.Forward();
  std::vector<mx_float> out2 = pred2.GetOutput(0);
  for (size_t i = 0; i < out.Data().size(); ++i) {
    if (out.Data()[i] - out2[i] > 1e-6f || out2[i] - out.Data()[i] > 1e-6f)
      return 3;
    std::printf("%f\n", out.Data()[i]);
  }
  return 0;
}
"""


def test_cpp_package_header(tmp_path):
    import shutil
    if shutil.which("g++") is None:
        pytest.skip("no g++")
    prefix, probe, expect = _export_model(tmp_path)
    src = tmp_path / "t.cc"
    src.write_text(_CPP_PROGRAM)
    exe = str(tmp_path / "tcc")
    inc = os.path.join(os.path.dirname(__file__), "..", "include")
    subprocess.run(["g++", "-std=c++14", "-O1", str(src), "-I", inc,
                    "-L", _NATIVE, "-lmxtpu_predict", "-o", exe,
                    "-Wl,-rpath," + os.path.abspath(_NATIVE)], check=True)
    env = dict(os.environ,
               PYTHONPATH=os.path.abspath(
                   os.path.join(os.path.dirname(__file__), "..")),
               JAX_PLATFORMS="cpu")
    res = subprocess.run([exe, prefix + "-symbol.json",
                          prefix + "-0001.params"], env=env,
                         capture_output=True, text=True, timeout=240)
    assert res.returncode == 0, (res.returncode, res.stderr)
    got = np.asarray([float(v) for v in res.stdout.split()],
                     np.float32).reshape(2, 3)
    np.testing.assert_allclose(got, expect, rtol=1e-5, atol=1e-6)


def test_amalgamation_builds_and_predicts(tmp_path):
    import shutil
    if shutil.which("g++") is None or shutil.which("python3-config") is None:
        pytest.skip("no g++/python3-config")
    sys_path = os.path.join(os.path.dirname(__file__), "..")
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "amalgamate", os.path.join(sys_path, "amalgamation",
                                   "amalgamate.py"))
    amal = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(amal)
    out = str(tmp_path / "dist")
    cc = amal.amalgamate(out)

    import subprocess as sp
    inc = sp.run(["python3-config", "--includes"], capture_output=True,
                 text=True).stdout.split()
    ld = sp.run(["python3-config", "--ldflags", "--embed"],
                capture_output=True, text=True).stdout.split()
    so = str(tmp_path / "libamal.so")
    sp.run(["g++", "-O2", "-std=c++17", "-fPIC", "-shared", cc] + inc +
           ld + ["-o", so], check=True)

    # drive the amalgamated .so from a FRESH process whose embedded
    # interpreter can only see the bundle -- proves the bundle is a
    # complete runtime, not just that the ABI compiled
    prefix, probe, expect = _export_model(tmp_path)
    driver = tmp_path / "drive.py"
    driver.write_text("""
import ctypes, sys
import numpy as np
lib = ctypes.CDLL(sys.argv[1])
lib.MXGetLastError.restype = ctypes.c_char_p
json_data = open(sys.argv[2], 'rb').read()
params = open(sys.argv[3], 'rb').read()
keys = (ctypes.c_char_p * 1)(b'data')
indptr = (ctypes.c_uint * 2)(0, 2)
shape = (ctypes.c_uint * 2)(2, 5)
h = ctypes.c_void_p()
rc = lib.MXPredCreate(json_data, params, len(params), 1, 0, 1, keys,
                      indptr, shape, ctypes.byref(h))
assert rc == 0, lib.MXGetLastError()
probe = (np.arange(10, dtype=np.float32) / 10.0)
assert lib.MXPredSetInput(h, b'data',
    probe.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), 10) == 0
assert lib.MXPredForward(h) == 0, lib.MXGetLastError()
out = np.empty(6, np.float32)
assert lib.MXPredGetOutput(h, 0,
    out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), 6) == 0
print(' '.join('%r' % float(v) for v in out))
""")
    env = dict(os.environ,
               PYTHONPATH=os.path.join(out, "bundle"),
               JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [os.sys.executable, str(driver), so, prefix + "-symbol.json",
         prefix + "-0001.params"],
        env=env, capture_output=True, text=True, timeout=240)
    assert res.returncode == 0, res.stderr
    got = np.asarray([float(v) for v in res.stdout.split()],
                     np.float32).reshape(2, 3)
    np.testing.assert_allclose(got, expect, rtol=1e-5, atol=1e-6)
