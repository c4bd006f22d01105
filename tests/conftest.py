"""Test configuration: force a virtual 8-device CPU mesh.

Mirrors the reference's strategy of faking multi-device with multiple CPU
contexts in one process (tests/python/unittest/test_multi_device_exec.py):
here we give XLA 8 host devices so jax.sharding Meshes exercise real
collectives without TPU hardware. Both variables are set before jax is
imported, so the tests never reach for a chip even where one exists.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()


# ---------------------------------------------------------------------------
# fast/slow partition (docs/testing.md): `-m fast` is the pre-merge tier
# (< 2 min); the full suite is the nightly tier. Files listed here spawn
# subprocesses (launchers, native builds, example scripts) or run
# multi-minute sweeps; everything else is fast by default.
# ---------------------------------------------------------------------------
SLOW_FILES = {
    "test_bench_contract.py",     # bench.py child process end to end
    "test_bf16_training.py",      # convergence runs
    "test_c_api.py",              # builds + runs pure-C LeNet training
    "test_c_predict.py",          # native predict builds
    "test_caffe_converter.py",    # converter round trips
    "test_checkpoint.py",         # orbax async + elastic restart
    "test_cpp_package.py",        # compiles + converges C++ LeNet
    "test_dist_launch.py",        # multi-process jax.distributed
    "test_gluon.py",              # model-zoo family forwards
    "test_image_det.py",          # detection aug pipelines
    "test_io.py",                 # record pipelines + process pools
    "test_legacy_params.py",      # model-zoo weight migration subprocess
    "test_module.py",             # fit() convergence runs
    "test_native_cpp.py",         # g++ builds
    "test_onnx_import.py",        # protobuf model imports
    "test_op_sweep.py",           # whole-registry sweep (minutes)
    "test_op_variants.py",        # parameter-grid sweeps
    "test_operator.py",
    "test_parallel.py",           # 8-device mesh shardings
    "test_pallas_attention.py",   # interpreter-mode kernels
    "test_pallas_rnn.py",
    "test_perl_binding.py",       # perl Makefile.PL build
    "test_r_binding.py",          # gcc typecheck
    "test_remat.py",
    "test_rnn.py",
    "test_sparse.py",
    "test_train_scripts.py",      # example/ scripts end to end
    "test_text_image.py",
    "test_nhwc_layout.py",        # resnet-block layout bit-compat (20s)
    "test_vision_ops.py",         # multibox/proposal/nms sweeps
    "test_gluon_contrib.py",      # conv-RNN cell learning runs
    "test_sparse_compact.py",     # 300k-row embedding training
    "test_extra_ops.py",          # deformable/psroi grids
    "test_legacy_api.py",         # FeedForward fit runs
    "test_jvm_binding.py",        # may build the native lib
    "test_aux.py",                # launcher dry-run subprocesses
    "test_gradcomp.py",           # bandwidth tool child interpreter
}


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running / subprocess-spawning test "
                   "(nightly tier; excluded from -m fast)")
    config.addinivalue_line(
        "markers", "fast: pre-merge tier, `pytest -m fast` < 2 min")
    # lock witness (docs/static_analysis.md "Lock witness"): armed
    # BEFORE any mxtpu import, and loaded by FILE PATH — `import
    # mxtpu.devtools.lockwitness` would run mxtpu/__init__ first and
    # every lock created during that import would be born unwrapped,
    # making accesses under those locks look unguarded.
    if os.environ.get("MXTPU_LOCK_WITNESS") == "1":
        import importlib.util
        import pathlib
        lw = pathlib.Path(__file__).resolve().parent.parent / \
            "mxtpu" / "devtools" / "lockwitness.py"
        spec = importlib.util.spec_from_file_location(
            "_mxtpu_lockwitness", str(lw))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        mod.install()


def pytest_collection_modifyitems(config, items):
    import pytest
    for item in items:
        fname = os.path.basename(str(item.fspath))
        if fname in SLOW_FILES or item.get_closest_marker("slow"):
            item.add_marker(pytest.mark.slow)
        else:
            item.add_marker(pytest.mark.fast)
