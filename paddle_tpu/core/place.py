"""Device placement abstraction.

Reference: paddle/platform/place.h:24,34,53 defines CPUPlace/CUDAPlace as a
boost::variant consumed by DeviceContext (paddle/platform/device_context.h:45).
Here a Place simply names a JAX backend + device ordinal; actual memory and
stream management is owned by PJRT/XLA, so there is no DeviceContext-style
stream plumbing — kernels are staged into a single XLA program instead.
"""

from __future__ import annotations

import dataclasses
import functools

import jax


@dataclasses.dataclass(frozen=True)
class Place:
    """A named device slot: backend + ordinal."""

    backend: str = ""  # "" = JAX default backend (TPU when present)
    device_id: int = 0

    @property
    def device(self) -> jax.Device:
        # a Place names a device THIS process can address: under
        # multi-process jax.distributed, jax.devices() is the global list
        # and its first entry belongs to process 0 — indexing it from
        # another process would pin the executor to hardware it cannot
        # touch (single-process: local == global, nothing changes)
        devs = jax.local_devices(backend=self.backend or None)
        return devs[self.device_id % len(devs)]

    def __repr__(self) -> str:
        return f"{self.__class__.__name__}({self.device_id})"


class CPUPlace(Place):
    def __init__(self, device_id: int = 0):
        super().__init__(backend="cpu", device_id=device_id)


class TPUPlace(Place):
    """The DEFAULT-backend place, not a promise of a TPU: it resolves to
    whatever backend JAX initialised — the TPU when one is attached, the
    CPU under `JAX_PLATFORMS=cpu` (the CPU-simulated mesh tests depend on
    that) and also when a TPU failed to initialise. Code that must run
    on the chip checks `jax.devices()[0].platform` itself, as
    `chip_smoke.py` does; it does not rely on this class."""

    def __init__(self, device_id: int = 0):
        super().__init__(backend="", device_id=device_id)


@functools.lru_cache(maxsize=None)
def default_place() -> Place:
    return TPUPlace(0)


def is_tpu_available() -> bool:
    try:
        return jax.devices()[0].platform == "tpu"
    except RuntimeError:
        return False
