"""``paddle.device`` namespace (ref: ``python/paddle/device/``)."""
from ..framework.device import (  # noqa: F401
    set_device, get_device, get_all_devices, device_count,
    is_compiled_with_cuda, is_compiled_with_rocm, is_compiled_with_xpu,
    is_compiled_with_tpu, is_compiled_with_cinn,
    is_compiled_with_custom_device, device_guard, Place, CPUPlace, TPUPlace,
    CUDAPlace, CustomPlace, XPUPlace,
)
from .plugin import (  # noqa: F401
    load_custom_runtime_lib, load_custom_device_plugins, registered_plugins)
from .xla_flags import (  # noqa: F401
    enable_overlap_flags, overlap_flags_active, OVERLAP_XLA_FLAGS)
from .compile_cache import place_compile_cache  # noqa: F401

__all__ = ["set_device", "get_device", "get_all_devices", "device_count",
           "is_compiled_with_cuda", "is_compiled_with_tpu", "cuda",
           "get_available_device", "get_available_custom_device",
           "load_custom_runtime_lib", "load_custom_device_plugins",
           "get_cudnn_version", "IPUPlace", "is_compiled_with_ipu",
           "get_all_device_type", "get_all_custom_device_type",
           "Stream", "Event", "current_stream", "set_stream",
           "stream_guard", "synchronize",
           "enable_overlap_flags", "overlap_flags_active",
           "place_compile_cache"]


def get_available_device():
    return get_all_devices()


def get_available_custom_device():
    return [d for d in get_all_devices() if d.startswith("tpu")]


class cuda:
    """Parity shim for paddle.device.cuda — maps to the TPU accelerator."""

    @staticmethod
    def device_count():
        return device_count()

    @staticmethod
    def synchronize(device=None):
        import jax
        # block until all dispatched work completes
        (jax.device_put(0) + 0).block_until_ready()

    @staticmethod
    def empty_cache():
        import gc
        gc.collect()

    @staticmethod
    def max_memory_allocated(device=None):
        return _mem_stat("peak_bytes_in_use")

    @staticmethod
    def memory_allocated(device=None):
        return _mem_stat("bytes_in_use")


# -- stream/event surface (ref device/__init__.py:410-877) ---------------
# XLA owns scheduling on TPU: one ordered stream per device, host-side
# synchronization is a block_until_ready. These objects keep the API so
# CUDA-era scripts run; "waiting" degrades to full-device sync.

def get_cudnn_version():
    """ref ``device/__init__.py``: None when not built with cuDNN."""
    return None


def is_compiled_with_ipu():
    return False


class IPUPlace:
    def __init__(self):
        raise RuntimeError("paddle_tpu is not compiled with IPU support")


def get_all_device_type():
    """ref: device types this build can drive (the jax platform name —
    a gpu backend must not masquerade as tpu)."""
    import jax
    kinds = {"cpu"}
    try:
        for d in jax.devices():
            kinds.add(d.platform)
    except Exception:
        pass
    return sorted(kinds)


def get_all_custom_device_type():
    return [t for t in get_all_device_type() if t not in ("cpu", "gpu")]


class Event:
    """ref ``device/__init__.py:410``. Records a point in the device
    timeline; on XLA the only observable point is "everything submitted
    so far is done", via synchronize."""

    def __init__(self, device=None, enable_timing=False, blocking=False,
                 interprocess=False):
        self._recorded = False

    def record(self, stream=None):
        self._recorded = True

    def query(self):
        return True  # XLA execution is ordered; nothing is "pending"

    def synchronize(self):
        synchronize()


class Stream:
    """ref ``device/__init__.py:555``. XLA has one compute stream per
    chip; this object exists so stream-annotated code runs unchanged."""

    def __init__(self, device=None, priority=2, blocking=False):
        self.device = device

    def record_event(self, event=None):
        ev = event or Event()
        ev.record(self)
        return ev

    def wait_event(self, event):
        synchronize()

    def wait_stream(self, stream):
        synchronize()

    def query(self):
        return True

    def synchronize(self):
        synchronize()


_current_stream = Stream()


def current_stream(device=None):
    return _current_stream


def set_stream(stream):
    global _current_stream
    prev = _current_stream
    _current_stream = stream
    return prev


class stream_guard:
    def __init__(self, stream):
        self._stream = stream

    def __enter__(self):
        self._prev = set_stream(self._stream)
        return self._stream

    def __exit__(self, *exc):
        set_stream(self._prev)
        return False


def synchronize(device=None):
    """Block until every submitted computation finished (ref
    ``device/__init__.py:877``). XLA dispatch is async and ORDERED per
    device, so joining on a fresh trailing computation joins everything
    submitted before it (same pattern as ``cuda.synchronize``);
    effects_barrier additionally joins effectful ones."""
    import jax
    try:
        jax.effects_barrier()
    except Exception:
        pass
    (jax.device_put(0) + 0).block_until_ready()


# -- cuda-namespace parity additions (alias the device-level surface;
# ref device/cuda/__init__.py) -------------------------------------------
cuda.Stream = Stream
cuda.Event = Event
cuda.current_stream = staticmethod(current_stream)
cuda.stream_guard = stream_guard


def _mem_stat(which, device=None):
    # the ONE allocator read every memory shim routes through: guarded
    # (never initializes a jax backend just to ask), 0 when absent
    from ..observability.memory import device_memory_stat
    return device_memory_stat(which)


def _memory_reserved(device=None):
    return _mem_stat("bytes_reserved") or _mem_stat("bytes_in_use")


def _max_memory_reserved(device=None):
    return _mem_stat("peak_bytes_in_use")


cuda.memory_reserved = staticmethod(_memory_reserved)
cuda.max_memory_reserved = staticmethod(_max_memory_reserved)


def _get_device_properties(device=None):
    import jax
    d = jax.local_devices()[0]
    class _Props:
        name = getattr(d, "device_kind", "cpu")
        major, minor = 0, 0
        total_memory = _mem_stat("bytes_limit")
        multi_processor_count = 1
    return _Props()


cuda.get_device_properties = staticmethod(_get_device_properties)
cuda.get_device_name = staticmethod(
    lambda device=None: _get_device_properties(device).name)
cuda.get_device_capability = staticmethod(lambda device=None: (0, 0))


class xpu:
    """``paddle.device.xpu`` parity shim (no XPU in a TPU build; the
    one exported name joins the ordered XLA stream like the others)."""

    @staticmethod
    def synchronize(device=None):
        return synchronize(device)
