"""Device / Place abstraction.

Reference parity: `paddle/fluid/platform/place.h:1` (CPUPlace/CUDAPlace/...)
and `paddle.set_device` (`python/paddle/device/__init__.py`). On TPU the
device identity maps to a `jax.Device`; multi-chip identity is expressed via
`jax.sharding.Mesh` (see paddle_tpu.parallel), not per-op placement.
"""
from __future__ import annotations

import jax


class Place:
    """Tagged device identity."""

    device_type = "unknown"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def jax_device(self):
        """The `jax.Device` this place names. A platform with no device in
        this process raises: `set_device('tpu')` on a host without a TPU
        is an error, never the CPU under another name. (`CPUPlace` on a
        TPU host asks for the CPU backend by name, so it still gets one.)"""
        platform = self._jax_platform()
        try:
            devs = jax.devices(platform)
        except RuntimeError as e:
            raise RuntimeError(
                f"{self!r} names a {platform} device and this process has "
                f"none (default backend: {jax.default_backend()})") from e
        return devs[self.device_id % len(devs)]

    def _jax_platform(self):
        return {"cpu": "cpu", "tpu": "tpu", "gpu": "gpu"}.get(self.device_type, "cpu")


class CPUPlace(Place):
    device_type = "cpu"


class TPUPlace(Place):
    device_type = "tpu"


class CUDAPlace(Place):  # accepted for API compat; maps to gpu backend if present
    device_type = "gpu"


def _default_place() -> Place:
    plat = jax.default_backend()
    if plat == "tpu":
        return TPUPlace(0)
    if plat == "gpu":
        return CUDAPlace(0)
    return CPUPlace(0)


_CURRENT_PLACE = [None]


def set_device(device) -> Place:
    """paddle.set_device('tpu') / 'cpu' / 'tpu:0'."""
    if isinstance(device, Place):
        place = device
    else:
        spec = str(device).lower()
        idx = 0
        if ":" in spec:
            spec, sidx = spec.split(":", 1)
            idx = int(sidx)
        if spec in ("tpu", "xla"):
            place = TPUPlace(idx)
        elif spec in ("gpu", "cuda"):
            place = CUDAPlace(idx)
        elif spec == "cpu":
            place = CPUPlace(idx)
        else:
            raise ValueError(f"unknown device {device!r}")
    device = place.jax_device()     # raises before anything is selected
    _CURRENT_PLACE[0] = place
    jax.config.update("jax_default_device", device)
    return place


def get_device() -> str:
    p = get_place()
    return f"{p.device_type}:{p.device_id}"


def get_place() -> Place:
    if _CURRENT_PLACE[0] is None:
        _CURRENT_PLACE[0] = _default_place()
    return _CURRENT_PLACE[0]


def is_compiled_with_tpu() -> bool:
    return any(d.platform == "tpu" for d in jax.devices())


def device_count() -> int:
    return jax.device_count()


class CUDAPinnedPlace(Place):
    """Accepted for API compat (pinned host memory has no TPU analogue —
    host staging buffers are runtime-managed); treated as host placement."""

    def __init__(self):
        super().__init__("cpu", 0)


class NPUPlace(Place):
    """Accepted for API compat; maps onto the single accelerator backend."""

    def __init__(self, idx=0):
        super().__init__("npu", idx)
