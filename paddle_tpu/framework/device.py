"""Device and place management.

TPU-native replacement for the reference's device layer:
 - ``phi::Place`` / ``CUDAPlace`` / ``CPUPlace`` (``paddle/phi/common/place.h``)
 - ``phi::DeviceManager`` enumeration (``paddle/phi/backends/device_manager.h:128``)
 - ``paddle.set_device`` (``python/paddle/device/__init__.py``)

On TPU, device enumeration comes from the PJRT client via ``jax.devices()``;
"place" maps to a jax Device, and a `device_guard` maps to
``jax.default_device``. There are no user-visible streams: XLA owns ordering
(the reference's stream/event machinery — ``paddle/phi/backends/stream.h`` —
is subsumed by the compiler's async scheduling).
"""
from __future__ import annotations

import contextlib

import jax

__all__ = [
    "Place", "CPUPlace", "TPUPlace", "CUDAPlace", "CustomPlace", "XPUPlace",
    "CUDAPinnedPlace",
    "set_device", "get_device", "get_all_devices", "device_count",
    "is_compiled_with_cuda", "is_compiled_with_rocm", "is_compiled_with_xpu",
    "is_compiled_with_tpu", "is_compiled_with_cinn",
    "is_compiled_with_custom_device", "device_guard", "get_jax_device",
    "on_tpu", "mosaic_can_lower", "pallas_dispatch",
]


def on_tpu() -> bool:
    """The one "am I on the chip" predicate. Kernel dispatch and Pallas
    interpret mode both ask here, so they cannot disagree. A backend that
    fails to initialize raises."""
    return jax.devices()[0].platform == "tpu"


def mosaic_can_lower() -> bool:
    """Whether a Pallas TPU kernel traced here can be lowered at all.

    GSPMD cannot partition a Mosaic kernel: under a multi-device mesh jax
    refuses the lowering ("Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map.") unless the call
    sits in a ``shard_map`` body that is manual over every mesh axis.
    The ambient abstract mesh says which case a trace is in."""
    am = jax.sharding.get_abstract_mesh()
    return (am.empty or am.size == 1
            or set(am.manual_axes) == set(am.axis_names))


def pallas_dispatch() -> bool:
    """The dispatch rule for every Pallas kernel: selected on a TPU, in a
    program Mosaic can lower. Platform and mesh only — never a runtime
    probe; a selected kernel the compiler refuses fails the program."""
    return on_tpu() and mosaic_can_lower()


class Place:
    """Base place: (device_type, index)."""

    device_type = "undefined"

    def __init__(self, index: int = 0):
        self._index = int(index)

    def get_device_id(self) -> int:
        return self._index

    def __repr__(self):
        return f"Place({self.device_type}:{self._index})"

    def __eq__(self, other):
        return (isinstance(other, Place)
                and other.device_type == self.device_type
                and other._index == self._index)

    def __hash__(self):
        return hash((self.device_type, self._index))

    def jax_device(self):
        devs = [d for d in jax.devices() if d.platform == self.device_type]
        if not devs and self.device_type == "cpu":
            # the host is always addressable, also beside an accelerator
            devs = jax.devices("cpu")
        if not devs:
            raise RuntimeError(f"No {self.device_type} device available")
        if self._index >= len(devs):
            raise RuntimeError(
                f"{self.device_type}:{self._index} out of range: "
                f"{len(devs)} {self.device_type} device(s) available")
        return devs[self._index]

    def is_cpu_place(self):
        return self.device_type == "cpu"

    def is_gpu_place(self):
        return False

    def is_tpu_place(self):
        return self.device_type == "tpu"


class CPUPlace(Place):
    device_type = "cpu"

    def __init__(self):
        super().__init__(0)


class TPUPlace(Place):
    device_type = "tpu"


# Parity aliases: reference scripts say CUDAPlace; on this framework the
# accelerator is the TPU.
class CUDAPlace(TPUPlace):
    pass


class XPUPlace(TPUPlace):
    pass


class CUDAPinnedPlace(Place):
    """Host staging-memory place. On TPU the analog of CUDA pinned memory
    is the host side of the PJRT transfer path; kept for API parity."""
    device_type = "cuda_pinned"

    def __init__(self):
        super().__init__(0)


class CustomPlace(Place):
    def __init__(self, device_type="tpu", index=0):
        super().__init__(index)
        self.device_type = device_type


_current_device: str | None = None


def _default_device_str() -> str:
    try:
        d = jax.devices()[0]
        return "cpu" if d.platform == "cpu" else f"tpu:{d.id}"
    except RuntimeError:
        return "cpu"


def set_device(device: str):
    """``paddle.set_device``: 'cpu', 'tpu', 'tpu:0' (also accepts 'gpu' as a
    parity alias for the accelerator)."""
    global _current_device
    device = device.lower().replace("gpu", "tpu").replace("xpu", "tpu")
    if device in ("tpu", "cpu"):
        device += ":0"
    kind, _, idx = device.partition(":")
    if kind not in ("cpu", "tpu"):
        raise ValueError(f"Unknown device {device!r}")
    place = CPUPlace() if kind == "cpu" else TPUPlace(int(idx or 0))
    jax.config.update("jax_default_device", place.jax_device())
    _current_device = f"{kind}:{idx or 0}" if kind != "cpu" else "cpu"
    return place


def get_device() -> str:
    return _current_device or _default_device_str()


def get_all_devices():
    return [("cpu" if d.platform == "cpu" else f"tpu:{d.id}") for d in jax.devices()]


def device_count() -> int:
    return len(jax.devices())


def get_jax_device(place=None):
    if place is None:
        dev = get_device()
        kind, _, idx = dev.partition(":")
        place = CPUPlace() if kind == "cpu" else TPUPlace(int(idx or 0))
    elif isinstance(place, str):
        kind, _, idx = place.lower().replace("gpu", "tpu").partition(":")
        place = CPUPlace() if kind == "cpu" else TPUPlace(int(idx or 0))
    return place.jax_device()


@contextlib.contextmanager
def device_guard(device: str):
    """Scoped default device (ref: ``paddle.static.device_guard``)."""
    prev = get_device()
    set_device(device)
    try:
        yield
    finally:
        set_device(prev)


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_cinn() -> bool:
    # XLA plays CINN's role and is always present.
    return True


def is_compiled_with_tpu() -> bool:
    return any(d.platform == "tpu" for d in jax.devices())


def is_compiled_with_custom_device(device_type: str = "tpu") -> bool:
    return True
