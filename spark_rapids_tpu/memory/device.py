"""TPU device manager: device discovery + HBM budget accounting bootstrap.

Reference: GpuDeviceManager.scala (initializeGpuAndMemory:150, initializeRmm:275).
On TPU the XLA runtime owns the physical HBM allocator, so the RMM-pool analogue
is byte *accounting* against a budget (allocFraction × HBM) plus the spill/retry
machinery in memory/ (SURVEY.md §2.4 TPU mapping note).
"""

from __future__ import annotations

import logging
import threading
from typing import Optional

from ..config import HBM_ALLOC_FRACTION, RapidsConf, default_conf

log = logging.getLogger("spark_rapids_tpu")

#: Published per-chip peaks, keyed by `device_kind` as JAX reports it. The one
#: table for the package and the benchmarks; a TPU that is not listed is an
#: error, not a default. Source: Google Cloud documentation, "TPU v5e"
#: (197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s
#: chip-to-chip interconnect).
DEVICE_PEAKS = {
    "TPU v5 lite": {"hbm_bytes": 16 * 1024 ** 3, "hbm_GBps": 819.0,
                    "bf16_TFLOPs": 197.0, "int8_TOPs": 393.0,
                    "ici_Gbps": 1600.0,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def device_peaks(device=None) -> dict:
    """The published peaks of `device` (default: the first device)."""
    import jax
    device = device or jax.devices()[0]
    try:
        return DEVICE_PEAKS[device.device_kind]
    except KeyError:
        raise RuntimeError(
            f"no published peaks for device_kind {device.device_kind!r}: add "
            f"it to memory/device.py DEVICE_PEAKS with its source") from None


def _hbm_bytes(device) -> int:
    """Total device memory the budget is a fraction of. A TPU reports it
    (`memory_stats()["bytes_limit"]`) or the bootstrap fails: guessing the
    size of a chip that will not say is how a budget overruns HBM. The CPU
    backend (tests, the oracle) has no device memory to report; its budget
    is nominal and takes the size of the chip the tests stand in for."""
    if device.platform != "tpu":
        return DEVICE_PEAKS["TPU v5 lite"]["hbm_bytes"]
    device_peaks(device)  # an unknown chip is an error before any sizing
    stats = device.memory_stats()
    if not stats or "bytes_limit" not in stats:
        raise RuntimeError(
            f"{device} ({device.device_kind}) reports no memory_stats() "
            f"bytes_limit; cannot size the HBM budget")
    return int(stats["bytes_limit"])


class TpuDeviceManager:
    _lock = threading.Lock()
    _initialized = False
    _device = None
    _hbm_budget_bytes: int = 0

    @classmethod
    def initialize(cls, conf: Optional[RapidsConf] = None) -> None:
        with cls._lock:
            if cls._initialized:
                return
            conf = conf or default_conf()
            import jax
            devices = jax.devices()
            cls._device = devices[0]
            total = _hbm_bytes(cls._device)
            frac = conf.get(HBM_ALLOC_FRACTION)
            cls._hbm_budget_bytes = int(total * frac)
            cls._initialized = True
            log.info("TpuDeviceManager: device=%s hbm_budget=%d bytes",
                     cls._device, cls._hbm_budget_bytes)

    @classmethod
    def device(cls):
        cls.initialize()
        return cls._device

    @classmethod
    def hbm_budget_bytes(cls) -> int:
        cls.initialize()
        return cls._hbm_budget_bytes

    @classmethod
    def synchronize(cls) -> None:
        """Block until outstanding device work completes (reference Cuda.deviceSynchronize)."""
        import jax
        (jax.device_put(0) + 0).block_until_ready()

    @classmethod
    def reset_for_tests(cls) -> None:
        with cls._lock:
            cls._initialized = False
