"""Engine facade: execution-ordering controls.

Capability parity with ``include/mxnet/engine.h`` + ``python/mxnet/
engine.py``'s user surface. The reference's threaded dependency engine
(versioned vars, RAW/WAR/WAW queues, ``src/engine/threaded_engine.h``) is
subsumed by JAX/XLA: every dispatch is already async with dataflow
ordering, so the *semantics* users relied on map as:

* ``WaitForAll``        -> :func:`waitall` — drain all in-flight device work
* ``WaitForVar``        -> ``NDArray.wait_to_read``
* ``MXNET_ENGINE_TYPE=NaiveEngine`` (synchronous debugging) ->
  ``set_engine_type('NaiveEngine')`` / env var — every eager op blocks
  until its result is ready, giving deterministic, gdb-able stepping
* bulk execution (``MXNET_EXEC_BULK_EXEC_*``) -> :func:`set_bulk_size` —
  in MXNet this batches engine pushes; under XLA whole graphs are already
  one computation, so the knob is accepted and recorded for parity.
"""
from __future__ import annotations

import os
import threading

import jax

__all__ = ["waitall", "set_bulk_size", "bulk", "set_engine_type",
           "engine_type", "is_synchronous"]

_state = threading.local()
_ENGINE_TYPE = os.environ.get("MXNET_ENGINE_TYPE", "ThreadedEnginePerDevice")
_BULK_SIZE = int(os.environ.get("MXNET_EXEC_BULK_EXEC_MAX_NODE_TRAIN", "15"))


def waitall():
    """Block until all async device work completes (Engine::WaitForAll)."""
    jax.effects_barrier()
    for d in jax.live_arrays():
        try:
            d.block_until_ready()
        except RuntimeError:
            continue   # deleted (donated) buffers are already "done"


def set_engine_type(name):
    """'NaiveEngine' forces synchronous eager execution (debug mode);
    any Threaded* name restores async dispatch."""
    global _ENGINE_TYPE
    if name not in ("NaiveEngine", "ThreadedEngine",
                    "ThreadedEnginePerDevice"):
        raise ValueError("unknown engine type %r" % name)
    _ENGINE_TYPE = name


def engine_type():
    return _ENGINE_TYPE


def is_synchronous():
    return _ENGINE_TYPE == "NaiveEngine"


def set_bulk_size(size):
    """Set bulk-execution segment size; returns the previous value
    (reference MXEngineSetBulkSize)."""
    global _BULK_SIZE
    prev = _BULK_SIZE
    _BULK_SIZE = int(size)
    return prev


class bulk:
    """Context manager bulking ops (reference engine.py:bulk). Under XLA
    this is advisory — jitted regions already fuse — but the API and
    nesting semantics are preserved."""

    def __init__(self, size):
        self._size = size
        self._old = None

    def __enter__(self):
        self._old = set_bulk_size(self._size)
        return self

    def __exit__(self, *a):
        set_bulk_size(self._old)
