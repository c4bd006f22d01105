"""mxtpu — a TPU-native deep learning framework with MXNet's capabilities.

A from-scratch re-design of Apache MXNet (incubating) v1.1 for TPU hardware:
JAX/XLA is the compute substrate (whole-graph jit instead of a per-op async
engine), Pallas supplies custom kernels, pjit/shard_map over device meshes
replace KVStore/NCCL/ps-lite for parallelism. The public API mirrors
MXNet's (nd/sym/module/gluon/autograd/kv/io/optimizer/metric) so users of
the reference find everything in the same places.
"""
from __future__ import annotations

__version__ = "0.1.0"

import os as _os



def _place_compile_cache():
    # The one place the persistent compile cache is set. A directory given
    # from outside (JAX_COMPILATION_CACHE_DIR) is jax's to read and is left
    # alone; otherwise the cache lives at a FIXED path in the checkout —
    # the path is part of the cache key, so a directory that moves (a
    # mkdtemp) never hits twice.
    if _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax as _jax
    _jax.config.update("jax_compilation_cache_dir", _os.path.join(
        _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
        ".jax_cache"))


_place_compile_cache()


def _join_process_group():
    # launched by tools/launch.py: join the process group NOW, before any
    # import below touches the backend (jax.distributed must come up
    # before the first computation; the reference bootstraps in
    # KVStore::Create via ps::StartAsync, kvstore_dist.h:50-55 — here
    # package import is the earliest safe point). Spawned helper
    # processes (DataLoader / record-iter decode workers) inherit the
    # env and re-import this package — they must NOT try to join with a
    # duplicate process_id, hence the MainProcess guard.
    import multiprocessing as _mp
    if _mp.current_process().name != "MainProcess":
        return
    import jax as _jax
    try:
        _jax.distributed.initialize(
            coordinator_address=_os.environ["MXTPU_COORDINATOR"],
            num_processes=int(_os.environ["MXTPU_NUM_PROCS"]),
            process_id=int(_os.environ["MXTPU_PROC_ID"]))
    except RuntimeError as e:
        # worker scripts may have initialized explicitly ("should only be
        # called once"); anything else (unreachable coordinator, bad
        # port) must fail LOUDLY — silently degrading to N independent
        # single-process runs trains N wrong models (the reference's
        # ps::StartAsync also fails hard)
        msg = str(e).lower()
        if "already" not in msg and "once" not in msg:
            raise


if _os.environ.get("MXTPU_COORDINATOR"):
    _join_process_group()

from .base import MXNetError, MXTPUError
from .context import Context, cpu, gpu, tpu, current_context, num_gpus, num_tpus
from . import ndarray
from . import ndarray as nd
from . import autograd
from . import random
from .ndarray import NDArray

# Populated as the build proceeds (symbol, module, gluon, io, kvstore, ...).
def _optional_imports():
    import importlib
    g = globals()
    for name, aliases in [
        ("symbol", ("sym",)), ("executor", ()), ("optimizer", ("opt",)),
        ("initializer", ("init",)), ("metric", ()), ("lr_scheduler", ()),
        ("io", ()), ("callback", ()), ("model", ()), ("module", ("mod",)),
        ("kvstore", ("kv",)), ("kvstore_server", ()),
        ("gluon", ()), ("parallel", ()),
        ("gradient_compression", ()), ("checkpoint", ()),
        ("resilience", ()), ("partition", ()), ("dist_hooks", ()),
        ("profiler", ()), ("recordio", ()), ("image", ()),
        ("test_utils", ()), ("visualization", ("viz",)), ("monitor", ()),
        ("rnn", ()), ("engine", ()), ("operator", ()), ("contrib", ()),
        ("rtc", ()), ("torch", ()), ("attribute", ()),
        ("log", ()), ("registry", ()), ("libinfo", ()),
        ("executor_manager", ()), ("misc", ()),
    ]:
        try:
            m = importlib.import_module("." + name, __name__)
        except ModuleNotFoundError as e:
            # only tolerate the submodule itself being absent (still being
            # built); real import errors inside present modules must surface.
            if e.name == __name__ + "." + name:
                continue
            raise
        g[name] = m
        for a in aliases:
            g[a] = m


_optional_imports()
if "attribute" in globals():
    AttrScope = attribute.AttrScope  # noqa: F821
if "symbol" in globals():
    Symbol = symbol.Symbol  # noqa: F821
if "executor" in globals():
    Executor = executor.Executor  # noqa: F821
