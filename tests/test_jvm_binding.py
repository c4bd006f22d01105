"""JVM binding gates (jvm-package/, the reference scala-package's JNA
rendering — see jvm-package/README.md).

Two tiers:
1. ABI-surface gate (always): every ``native`` method declared in
   CApi.java must resolve in libmxtpu_c.so / libmxtpu_predict.so via
   ctypes — catches symbol renames/removals with no JVM present.
2. Runtime gate (JDK + jna.jar required): compile the package with
   javac and run ml.mxtpu.SmokeTest against the real libraries. Skipped
   with a clear reason when no JDK exists (this build image has none).
"""
import ctypes
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
JVM = os.path.join(ROOT, "jvm-package")
CAPI_JAVA = os.path.join(JVM, "src", "main", "java", "ml", "mxtpu",
                         "CApi.java")
NATIVE = os.path.join(ROOT, "mxtpu", "_native")

# slow: toolchain (make builds the native libraries; javac/gcc hosts)
pytestmark = pytest.mark.slow


def _declared_functions():
    """Names of the C functions CApi.java binds (JNA interface methods:
    'int MXFoo(' / 'String MXGetLastError(')."""
    src = open(CAPI_JAVA).read()
    names = re.findall(r"^\s+(?:int|String)\s+(MX\w+)\s*\(", src,
                       re.MULTILINE)
    assert len(names) >= 20, names
    return names


def test_capi_java_symbols_resolve():
    libs = []
    for so in ("libmxtpu_c.so", "libmxtpu_predict.so"):
        path = os.path.join(NATIVE, so)
        if not os.path.exists(path):
            subprocess.run(["make", "-C", NATIVE], check=True,
                           capture_output=True)
        libs.append(ctypes.CDLL(path))
    missing = []
    for name in _declared_functions():
        if not any(hasattr(lib, name) for lib in libs):
            missing.append(name)
    assert not missing, "CApi.java declares unknown C symbols: %s" % missing


def test_jvm_smoke(tmp_path):
    javac = shutil.which("javac")
    java = shutil.which("java")
    jna = os.environ.get("MXTPU_JNA_JAR")
    if not (javac and java):
        pytest.skip("no JDK in this image (jvm-package runtime gate "
                    "runs where javac/java exist; the ABI-surface gate "
                    "above ran)")
    if not (jna and os.path.exists(jna)):
        pytest.skip("MXTPU_JNA_JAR not set (jna.jar 5.x needed)")
    classes = tmp_path / "classes"
    classes.mkdir()
    srcs = [str(p) for p in
            (tmp_path / "x").parent.glob("nonexistent")]  # placeholder
    srcs = [os.path.join(JVM, "src", "main", "java", "ml", "mxtpu", f)
            for f in os.listdir(os.path.join(JVM, "src", "main", "java",
                                             "ml", "mxtpu"))]
    subprocess.run([javac, "-cp", jna, "-d", str(classes)] + srcs,
                   check=True, capture_output=True, text=True)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [java, "-cp", "%s:%s" % (jna, classes),
         "-Djna.library.path=" + NATIVE, "ml.mxtpu.SmokeTest"],
        capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr[-1500:]
    assert "JVM_SMOKE_OK" in out.stdout, out.stdout


def test_c_hosted_smoke(tmp_path):
    """Execute SmokeTest.java's exact call sequence without a JVM: the
    C harness (jvm-package/smoke_harness.c) drives the same symbols in
    the same order against the real libmxtpu_c.so, so the binding's
    call pattern has actually RUN in this image — JNA itself adds only
    argument marshalling on top of these calls. Where a JDK exists the
    real Java gate above runs too."""
    if shutil.which("gcc") is None:
        pytest.skip("no gcc")
    subprocess.run(["make", "-C", NATIVE, "libmxtpu_c.so"],
                   check=True, capture_output=True)
    exe = str(tmp_path / "smoke_harness")
    subprocess.run(
        ["gcc", "-O1", os.path.join(JVM, "smoke_harness.c"),
         "-I", ROOT, "-L", NATIVE, "-lmxtpu_c",
         "-Wl,-rpath," + NATIVE, "-lm", "-o", exe],
        check=True, capture_output=True, text=True)
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu")
    out = subprocess.run([exe], capture_output=True, text=True,
                         timeout=300, env=env)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "JVM_SMOKE_OK" in out.stdout, out.stdout
    assert "C_HOSTED_JVM_SEQUENCE_OK" in out.stdout, out.stdout
