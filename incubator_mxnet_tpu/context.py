"""Device contexts.

TPU-native re-design of the reference ``Context`` (reference:
include/mxnet/base.h struct Context; python/mxnet/context.py).  The reference
enumerates cpu/gpu/cpu_pinned/cpu_shared devices and every NDArray/op carries
a Context; here a Context resolves to a concrete ``jax.Device`` and array
placement is done with ``jax.device_put`` — XLA/PJRT owns streams, so there is
no stream manager layer.

``tpu(i)`` is first-class.  ``gpu(i)`` is accepted for script portability and
resolves to the i-th accelerator (on this stack: the TPU); this is the
"switch your script's context line and keep going" migration story.

A context names a device that exists or resolving it raises: ``tpu(i)`` on
a host without an accelerator and ``cpu(i)`` in a process whose jax has no
CPU backend are both ``MXNetError`` — nothing is ever substituted.  The
DEFAULT context follows jax's default backend (:func:`current_context`),
so a script that never names a device runs on the chip when there is one.
"""
from __future__ import annotations

import threading
from typing import Optional

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "cpu_pinned", "cpu_shared",
           "current_context", "num_gpus", "num_tpus", "current_device",
           "Device", "backend_in_use"]

_in_use = False


def _jax():
    """jax, noting that this process is about to hold a device backend
    (every device question below goes through here)."""
    global _in_use
    import jax
    _in_use = True
    return jax


def backend_in_use() -> bool:
    """True once this process has asked jax for a device through a
    Context.  A chip belongs to ONE process, so code that may run in a
    process that must stay off the chip — the router, the supervisor,
    their flight-recorder dumps and metric scrapes — checks this before
    any call that would bring a backend up (``jax.devices()``,
    ``jax.live_arrays()``)."""
    return _in_use


class Context:
    """A device context ``(device_type, device_id)``.

    Supports use as a ``with`` scope to set the default context, mirroring
    the reference (reference: python/mxnet/context.py Context.__enter__).
    """

    # numeric codes kept identical to the reference for serialization parity
    # (reference: include/mxnet/base.h DeviceType)
    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "cpu_shared", 6: "tpu"}
    devstr2type = {v: k for k, v in devtype2str.items()}

    _default = threading.local()

    def __init__(self, device_type, device_id: int = 0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        elif isinstance(device_type, str):
            if device_type not in self.devstr2type:
                raise MXNetError(f"unknown device type {device_type!r}")
            self.device_typeid = self.devstr2type[device_type]
            self.device_id = device_id
        else:
            self.device_typeid = int(device_type)
            self.device_id = device_id
        self._old_ctx: Optional[Context] = None

    # -- identity ----------------------------------------------------------
    @property
    def device_type(self) -> str:
        return self.devtype2str[self.device_typeid]

    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_typeid == other.device_typeid
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    __str__ = __repr__

    # -- jax resolution ----------------------------------------------------
    def jax_device(self):
        """Resolve to a concrete ``jax.Device``.

        'tpu'/'gpu' both mean "the i-th accelerator of the live jax backend";
        'cpu'/'cpu_pinned'/'cpu_shared' mean the host CPU backend (pinned /
        shared distinctions are meaningless under PJRT unified host memory).
        """
        jax = _jax()
        if self.device_type in ("cpu", "cpu_pinned", "cpu_shared"):
            try:
                devs = jax.local_devices(backend="cpu")
            except RuntimeError as e:
                raise MXNetError(
                    f"{self}: this process's jax has no CPU backend "
                    f"({e})") from e
            return devs[min(self.device_id, len(devs) - 1)]
        devs = _accelerators()
        if not devs:
            raise MXNetError(
                f"{self}: no accelerator in this process (jax default "
                f"backend is {jax.default_backend()!r})")
        if self.device_id >= len(devs):
            raise MXNetError(
                f"{self}: only {len(devs)} accelerator device(s) present")
        return devs[self.device_id]

    # -- default-context scope --------------------------------------------
    def __enter__(self):
        self._old_ctx = getattr(Context._default, "value", None)
        Context._default.value = self
        return self

    def __exit__(self, *exc):
        Context._default.value = self._old_ctx
        return False

    # parity helper (reference Context::empty_cache is a GPU-pool op; XLA
    # owns the allocator so this is a best-effort no-op)
    def empty_cache(self):
        pass


# jax>=0.4 calls these Devices; export an alias for mxnet-2.x-style code.
Device = Context


def _accelerators():
    """This process's non-CPU jax devices.  LOCAL devices only: in a
    multi-process job another host's chips are non-addressable, and
    ``tpu(i)`` always means "my i-th chip" (reference Context
    semantics)."""
    return [d for d in _jax().local_devices() if d.platform != "cpu"]


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    return Context("cpu_pinned", device_id)


def cpu_shared(device_id: int = 0) -> Context:
    return Context("cpu_shared", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def gpu(device_id: int = 0) -> Context:
    """Accepted for portability of reference scripts; resolves to the i-th
    accelerator (TPU on this stack)."""
    return Context("gpu", device_id)


def num_tpus() -> int:
    return len(_accelerators())


def num_gpus() -> int:
    """Parity with ``mx.context.num_gpus`` (reference: python/mxnet/context.py);
    counts accelerators."""
    return num_tpus()


def current_context() -> Context:
    """The default context: the innermost ``with ctx:`` scope of this
    thread, else device 0 of jax's default backend — ``tpu(0)`` on a host
    whose jax sees a chip, ``cpu(0)`` otherwise (``JAX_PLATFORMS=cpu``
    included).  Decided from what jax reports, never from a flag, so
    parameters, caches and inputs created without a ``ctx=`` land where
    the compiled programs run."""
    ctx = getattr(Context._default, "value", None)
    if ctx is not None:
        return ctx
    return cpu(0) if _jax().default_backend() == "cpu" else tpu(0)


current_device = current_context
