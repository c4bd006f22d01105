"""Where arrays go, and what proves it (PR 21).

The one rule lives in ``mxtpu/context.py``: with no context argument
everything sits on device 0 of JAX's default backend — the CPU here, the TPU
on the chip machine, where ``chip_smoke.py`` asserts the same legs at full
width. These tests pin the rule, the entry points that must not override it,
the compile-cache placement, and run ``chip_smoke.py``'s legs at toy size.
"""
import importlib.util
import os
import re
import subprocess
import sys
import threading

import pytest

import jax

import mxtpu as mx

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_ROOT, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- the rule ----------------------------------------------------------------

def test_default_context_follows_the_default_backend():
    assert jax.default_backend() == "cpu"
    assert mx.current_context() == mx.cpu(0)
    assert mx.nd.zeros((2,))._data.devices() == {jax.devices()[0]}
    # a fresh thread resolves the same default, not a hard-coded one
    seen = []
    t = threading.Thread(target=lambda: seen.append(mx.current_context()))
    t.start()
    t.join(timeout=30)
    assert seen == [mx.cpu(0)]


def test_default_context_is_tpu_when_the_backend_is(monkeypatch):
    from mxtpu.context import Context
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(Context, "_default_ctx", threading.local())
    assert Context.default_ctx() == mx.tpu(0)


@pytest.mark.parametrize("ctx", [mx.tpu(0), mx.gpu(0)])
def test_naming_a_chip_without_one_raises(ctx):
    with pytest.raises(RuntimeError, match="no tpu backend"):
        ctx.jax_device()
    assert mx.num_tpus() == 0 and mx.num_gpus() == 0


def test_entry_points_pass_no_cpu_context():
    """Motivation 1 of ISSUE 21: none of the normal entry points pins its
    arrays to the host, and no production site picks a kernel route from
    the process-wide default backend."""
    pinned = re.compile(r"context=mx\.cpu\(\)|ctx=mx\.cpu\(\)|else cpu\(\)")
    roots = ["example/image-classification", "example/char_lm",
             "example/moe_transformer", "mxtpu/serving"]
    hits = []
    for root in roots:
        path = os.path.join(_ROOT, root)
        files = [path] if path.endswith(".py") else [
            os.path.join(d, f) for d, _, fs in os.walk(path)
            for f in fs if f.endswith(".py")]
        for f in files:
            for i, line in enumerate(open(f), 1):
                if pinned.search(line):
                    hits.append("%s:%d" % (os.path.relpath(f, _ROOT), i))
    assert not hits, hits
    launch = open(os.path.join(_ROOT, "tools", "launch.py")).read()
    replica = launch[launch.index("def _spawn_serving_replica"):
                     launch.index("def _parse_scale")]
    assert 'JAX_PLATFORMS="cpu"' not in replica
    assert "mkdtemp" not in launch[launch.index("prewarm_dir ="):
                                   launch.index("if args.ps_respawn")]
    routed = []
    for d, _, fs in os.walk(os.path.join(_ROOT, "mxtpu")):
        for f in fs:
            if f.endswith(".py") and f != "context.py":
                for i, line in enumerate(open(os.path.join(d, f)), 1):
                    if "default_backend()" in line:
                        routed.append("%s:%d" % (f, i))
    assert not routed, routed


def test_launcher_keeps_one_process_per_chip():
    launch = _load("tools/launch.py", "launch_mod")

    def args(**kw):
        base = dict(serve=0, serve_max=0, num_workers=1, scale=None,
                    autoscale=False)
        base.update(kw)
        return type("A", (), base)()

    cpu = {"JAX_PLATFORMS": "cpu"}
    plat = launch._child_platform
    # the lone replica may take the chip; its client workers may not
    assert plat(args(serve=1), {}, "replica") == {}
    assert plat(args(serve=1), {}, "worker") == cpu
    # several replicas (now or after a scale-up) are CPU processes
    assert plat(args(serve=2), {}, "replica") == cpu
    assert plat(args(serve=1, serve_max=2), {}, "replica") == cpu
    # the lone worker of a plain launch may take the chip; two may not
    assert plat(args(), {}, "worker") == {}
    assert plat(args(num_workers=2), {}, "worker") == cpu
    assert plat(args(scale="x"), {}, "worker") == cpu
    # the chip machine exports JAX_PLATFORMS=tpu,cpu: still one owner
    assert plat(args(num_workers=2), {"JAX_PLATFORMS": "tpu,cpu"},
                "worker") == cpu
    assert plat(args(num_workers=2), {"JAX_PLATFORMS": "cpu"},
                "worker") == {}


# -- the flagship command is fused -------------------------------------------

def test_kvstore_instance_route_engages_the_fused_step():
    """``common/fit.py`` hands ``Module.fit`` a KVStore INSTANCE; on one
    device with nothing to reduce it must count like the string."""
    from mxtpu.model import _create_kvstore
    kv = mx.kvstore.create("device")
    assert _create_kvstore(kv, 1, {}) == (None, False)
    assert _create_kvstore(kv, 2, {})[0] is kv
    squeezed = mx.kvstore.create("device")
    squeezed.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    assert _create_kvstore(squeezed, 1, {})[0] is squeezed


# -- compile cache -------------------------------------------------------------

def test_compile_cache_rule():
    code = ("import mxtpu, jax; "
            "print(jax.config.jax_compilation_cache_dir)")
    base = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    base.update(JAX_PLATFORMS="cpu", PYTHONPATH=_ROOT)

    def run(env):
        return subprocess.run([sys.executable, "-c", code], env=env,
                              capture_output=True, text=True, timeout=120,
                              check=True).stdout.strip()

    assert run(base) == os.path.join(_ROOT, ".jax_cache")
    # set from outside: jax reads the variable, the program sets nothing
    assert run(dict(base, JAX_COMPILATION_CACHE_DIR="/x/elsewhere")) \
        == "/x/elsewhere"
    src = open(os.path.join(_ROOT, "mxtpu", "__init__.py")).read()
    assert src.count("jax_compilation_cache_dir") == 1
    for other in ("chip_smoke.py", "tools/launch.py"):
        assert "compilation_cache_dir\"" not in \
            open(os.path.join(_ROOT, other)).read(), other


# -- chip_smoke.py at toy size ---------------------------------------------------

@pytest.fixture(scope="module")
def smoke():
    return _load("chip_smoke.py", "chip_smoke_mod")


def test_no_tpu_means_no_metric():
    """As a process: with no TPU the script exits non-zero, says so once,
    and prints nothing that parses as a result (a CPU number is never
    written under a TPU metric's name)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_ROOT)
    res = subprocess.run([sys.executable, os.path.join(_ROOT, "chip_smoke.py")],
                         capture_output=True, text=True, timeout=300,
                         env=env)
    assert res.returncode != 0
    said = [l for l in res.stderr.splitlines() if "no TPU" in l]
    assert len(said) == 1, res.stderr[-500:]
    for line in res.stdout.splitlines():
        assert not line.lstrip().startswith("{"), line
        assert "img" not in line and "images/sec" not in line, line


def test_chip_smoke_main_refuses_the_cpu(smoke, capsys):
    assert smoke.main([]) == 1
    out, err = capsys.readouterr()
    assert "no TPU" in err
    assert "{" not in out          # the device line, and no result


def test_chip_smoke_legs_at_toy_size(smoke, tmp_path, capsys):
    sz, wd = smoke.TOY, str(tmp_path)
    trained = smoke.leg_train(sz, "cpu", wd)
    assert trained["mod"]._fused is not None
    smoke.leg_serve(sz, "cpu", trained)
    smoke.leg_generate(sz, "cpu", wd)
    smoke.leg_kernels(sz, "cpu")
    smoke.leg_mesh(sz, "cpu", wd, trained["losses"][0])
    out = capsys.readouterr().out
    for leg in ("train:", "serve:", "generate:", "kernels:", "mesh:"):
        assert any(line.startswith(leg) for line in out.splitlines()), leg
    # a leg that meets a wrong device fails instead of reporting it
    with pytest.raises(AssertionError, match="wanted only tpu"):
        smoke.leg_train(sz, "tpu", wd)
