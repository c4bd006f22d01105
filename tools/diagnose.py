#!/usr/bin/env python
"""Environment diagnostic (reference tools/diagnose.py): OS / hardware /
python / mxtpu / backend sections, printable into bug reports.

The backend section asks JAX for its devices in THIS process — a chip
belongs to one process at a time, so the diagnostic starts no child that
would reach for it.

Usage: python tools/diagnose.py [--skip-backend]
"""
from __future__ import annotations

import argparse
import os
import platform

import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def section(title):
    print("-" * 24)
    print(title)
    print("-" * 24)


def check_os():
    section("Platform")
    print("system   :", platform.system(), platform.release())
    print("machine  :", platform.machine())
    print("version  :", platform.version())
    print("node     :", platform.node())


def check_hardware():
    section("Hardware")
    print("cpu_count:", os.cpu_count())
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith(("MemTotal", "MemAvailable")):
                    print(line.strip())
    except IOError:
        pass
    try:
        with open("/proc/cpuinfo") as f:
            models = [l.split(":", 1)[1].strip() for l in f
                      if l.startswith("model name")]
        if models:
            print("cpu model:", models[0], "x%d" % len(models))
    except IOError:
        pass


def check_python():
    section("Python")
    print("version  :", sys.version.replace("\n", " "))
    print("exe      :", sys.executable)
    for mod in ("numpy", "jax", "jaxlib", "flax", "optax", "orbax",
                "PIL", "cv2", "pandas", "torch"):
        try:
            m = __import__(mod)
            print("%-9s: %s" % (mod, getattr(m, "__version__", "present")))
        except ImportError:
            print("%-9s: NOT INSTALLED" % mod)


def check_mxtpu():
    section("mxtpu")
    try:
        import mxtpu
        print("version  :", getattr(mxtpu, "__version__", "dev"))
        print("path     :", os.path.dirname(mxtpu.__file__))
        from mxtpu.ops.registry import _REGISTRY
        canonical = {op.name for op in _REGISTRY.values()}
        print("ops      : %d canonical (%d incl. aliases)"
              % (len(canonical), len(_REGISTRY)))
        so = os.path.join(os.path.dirname(mxtpu.__file__), "_native")
        native = [f for f in os.listdir(so)
                  if f.endswith(".so")] if os.path.isdir(so) else []
        print("native   :", ", ".join(native) if native
              else "(not built; make -C mxtpu/_native)")
    except Exception as e:
        print("IMPORT FAILED:", repr(e))


def check_backend():
    section("Accelerator backend")
    print("JAX_PLATFORMS =", os.environ.get("JAX_PLATFORMS", "(unset)"))
    print("XLA_FLAGS     =", os.environ.get("XLA_FLAGS", "(unset)"))
    import jax
    import mxtpu
    t0 = time.time()
    devs = jax.devices()
    print("device 0 : %s (%s), %d device(s)  [%.1fs]"
          % (devs[0].platform, devs[0].device_kind, len(devs),
             time.time() - t0))
    print("default  :", mxtpu.context.describe_device())
    print("cache    :", jax.config.jax_compilation_cache_dir)


def check_env():
    section("MXTPU_* / MXNET_* environment")
    found = False
    for k in sorted(os.environ):
        if k.startswith(("MXTPU_", "MXNET_", "JAX_", "XLA_")):
            print("%s=%s" % (k, os.environ[k]))
            found = True
    if not found:
        print("(none set)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-backend", action="store_true",
                    help="do not initialise the accelerator backend")
    args = ap.parse_args()
    check_os()
    check_hardware()
    check_python()
    check_mxtpu()
    check_env()
    if not args.skip_backend:
        check_backend()


if __name__ == "__main__":
    main()
