"""Device discovery and selection.

Reference equivalent: ``DeviceManager`` singleton that discovers CPU + CUDA
devices at startup and serves ``getCPU()/getGPU(i)`` lookups
(``/root/reference/src/device/device_manager.cpp:22-61``,
``include/device/device_manager.hpp:74-76``).

On TPU the platform runtime (PJRT) already owns discovery; this module is a
thin, dependency-free façade so the rest of the framework never touches
``jax.devices()`` directly and tests can force the CPU backend.
"""

from __future__ import annotations

import functools
import os
from dataclasses import dataclass
from typing import List, Optional

import jax


@dataclass(frozen=True)
class DeviceInfo:
    """Summary of one accelerator chip (reference: ``Device`` facade,
    ``include/device/device.hpp:12-43``)."""

    id: str           # e.g. "TPU:0", "CPU:0"
    platform: str     # "tpu" | "cpu" | "gpu"
    index: int
    device: jax.Device

    @property
    def is_accelerator(self) -> bool:
        return self.platform not in ("cpu",)


class DeviceManager:
    """Process-wide device registry (reference:
    ``DeviceManager::getInstance()``, ``device_manager.hpp:9``).

    Unlike the reference there is no allocation API here: array placement is
    expressed with ``jax.device_put`` / shardings, and HBM allocation is owned
    by PJRT.
    """

    _instance: Optional["DeviceManager"] = None

    def __init__(self) -> None:
        self._devices: List[DeviceInfo] = []
        self._discover()

    @classmethod
    def instance(cls) -> "DeviceManager":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def _discover(self) -> None:
        for d in jax.devices():
            plat = d.platform
            self._devices.append(
                DeviceInfo(id=f"{plat.upper()}:{d.id}", platform=plat, index=d.id, device=d)
            )
        # CPU host devices are always reachable even when an accelerator is the
        # default backend (reference always registers "CPU:0",
        # device_manager.cpp:27-33).
        if all(info.platform != "cpu" for info in self._devices):
            try:
                for d in jax.devices("cpu"):
                    self._devices.append(
                        DeviceInfo(id=f"CPU:{d.id}", platform="cpu", index=d.id, device=d)
                    )
            except RuntimeError:
                pass

    # -- lookups (reference: getCPU()/getGPU(i), device_manager.hpp:74-76) --
    def all(self) -> List[DeviceInfo]:
        return list(self._devices)

    def accelerators(self) -> List[DeviceInfo]:
        return [d for d in self._devices if d.is_accelerator]

    def cpu(self, index: int = 0) -> DeviceInfo:
        cpus = [d for d in self._devices if d.platform == "cpu"]
        if not cpus:
            raise RuntimeError("no CPU device registered")
        return cpus[index]

    def get(self, device_id: str) -> DeviceInfo:
        for d in self._devices:
            if d.id == device_id:
                return d
        raise KeyError(f"unknown device id {device_id!r}")

    def default(self) -> DeviceInfo:
        accs = self.accelerators()
        return accs[0] if accs else self._devices[0]


def require_tpu(what: str) -> None:
    """Guard for the main-path entry points (the example trainers,
    ``bench.py``, ``chip_smoke.py``): raise unless JAX came up on a TPU.

    The one route to the CPU is JAX's own switch, ``JAX_PLATFORMS=cpu`` in
    the environment (tier-1 and the verify recipe use it). Without it, a
    host where no chip was found would train or measure on the CPU and
    exit 0; this says so instead. Importing the package never calls this."""
    backend = jax.default_backend()
    if backend == "tpu":
        return
    asked = os.environ.get("JAX_PLATFORMS", "").strip().lower()
    if backend == "cpu" and asked == "cpu":
        return
    dev = jax.devices()[0]
    raise RuntimeError(
        f"{what}: JAX came up on backend {backend!r} "
        f"({len(jax.devices())} x {dev.device_kind}), not 'tpu' "
        f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}). This entry "
        f"point runs on the TPU; to run it on the CPU on purpose, export "
        f"JAX_PLATFORMS=cpu.")


def local_devices() -> List[jax.Device]:
    return jax.local_devices()


def device_count() -> int:
    return jax.device_count()


@functools.lru_cache(maxsize=None)
def default_device() -> jax.Device:
    return DeviceManager.instance().default().device
