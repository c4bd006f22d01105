"""Device context model.

Capability parity with ``include/mxnet/base.h:142-168`` (Context: kCPU/kGPU/
kCPUPinned/kCPUShared) re-designed for TPU: a Context names a JAX device.
``tpu`` is the first-class accelerator type; ``gpu`` is accepted as an alias
for it so reference-written scripts keep running.

The one rule for where arrays go: with no context argument, everything
lives on device 0 of JAX's default backend — the TPU on a machine that has
one, the CPU under ``JAX_PLATFORMS=cpu``. ``mx.cpu()`` is for what is meant
to live on the host; ``mx.tpu(i)``/``mx.gpu(i)`` name a chip and raise when
there is none. Nothing here substitutes one kind of device for another.

Unlike MXNet there is no per-device stream/engine pair to manage: XLA owns
scheduling. A Context resolves lazily to a ``jax.Device`` so that importing
mxtpu never forces backend initialisation.
"""
from __future__ import annotations

import threading

import jax

__all__ = ["Context", "cpu", "gpu", "tpu", "current_context", "num_gpus",
           "num_tpus", "describe_device"]


class Context:
    """A device context (device_type, device_id) resolving to a jax.Device."""

    # MXNet device mask values (base.h:142-168) kept for API parity.
    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "cpu_shared", 6: "tpu"}
    devstr2type = {v: k for k, v in devtype2str.items()}

    _default_ctx = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            if device_type not in self.devstr2type:
                raise ValueError("unknown device type %r" % (device_type,))
            self.device_typeid = self.devstr2type[device_type]
            self.device_id = device_id
        self._old_ctx = None

    @property
    def device_type(self):
        return self.devtype2str[self.device_typeid]

    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_typeid == other.device_typeid
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    __str__ = __repr__

    # -- jax resolution ---------------------------------------------------
    def jax_device(self):
        """Resolve to a concrete jax.Device (lazy; raises when the process
        has no device of this type)."""
        devs = _devices_for(self.device_type)
        return devs[self.device_id % len(devs)]

    # -- scope protocol (with mx.Context(...):) ---------------------------
    def __enter__(self):
        self._old_ctx = Context.default_ctx()
        Context._default_ctx.value = self
        return self

    def __exit__(self, *args):
        Context._default_ctx.value = self._old_ctx

    @classmethod
    def default_ctx(cls):
        """The thread's default context: device 0 of JAX's default
        backend until a ``with ctx:`` scope says otherwise."""
        if not hasattr(cls._default_ctx, "value"):
            cls._default_ctx.value = Context(
                "tpu" if jax.default_backend() == "tpu" else "cpu", 0)
        return cls._default_ctx.value


def _devices_for(device_type):
    """The jax devices a device-type string names; raises when the
    process has none.

    Uses *local* devices: in a multi-process (jax.distributed) run,
    jax.devices() lists every process's devices and only this process's
    are addressable — a Context must never resolve to a peer's device
    (caught by tests/nightly/dist_worker.py on rank 1)."""
    backend = "cpu" if device_type.startswith("cpu") else "tpu"
    try:
        return jax.local_devices(backend=backend)
    except RuntimeError as e:
        raise RuntimeError(
            "mx.%s(): this process has no %s backend (JAX_PLATFORMS=%r, "
            "default backend %r): %s" % (
                device_type, backend, jax.config.jax_platforms,
                jax.default_backend(), e)) from None


def describe_device(ctx=None):
    """One line naming where ``ctx`` (default: the current context)
    puts arrays — what the entry points log once at start-up."""
    ctx = ctx or current_context()
    dev = ctx.jax_device()
    return "%s: platform %s, device_kind %r, %d local device(s)" % (
        ctx, dev.platform, dev.device_kind,
        len(jax.local_devices(backend=dev.platform)))


def cpu(device_id=0):
    """Return a CPU context."""
    return Context("cpu", device_id)


def gpu(device_id=0):
    """Alias for :func:`tpu` (API parity with mx.gpu)."""
    return Context("gpu", device_id)


def tpu(device_id=0):
    """Return a TPU context — the first-class accelerator of this framework."""
    return Context("tpu", device_id)


def num_gpus():
    return num_tpus()


def num_tpus():
    try:
        return len(_devices_for("tpu"))
    except RuntimeError:
        return 0


def current_context():
    """The default context of the current scope."""
    return Context.default_ctx()
