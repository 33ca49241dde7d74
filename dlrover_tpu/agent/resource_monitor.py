"""Agent-side node resource monitor.

Reference analog: dlrover/python/elastic_agent/monitor/resource.py
(ResourceMonitor: psutil CPU/mem + pynvml GPU -> master every 15s). TPU
differences: host stats come from psutil here in the agent; HBM usage can
only be observed from inside the JAX process that owns the chips, so the
trainer reports it separately (trainer/elastic_trainer.py) and the master
merges the two partial reports (fields <= 0 mean "not measured").
"""

from __future__ import annotations

import threading

from dlrover_tpu.common.log import get_logger
from dlrover_tpu.telemetry.metrics import registry

logger = get_logger(__name__)

# set from the TRAINER process (the one that owns the chips) and shipped
# to the master inside its pushed registry snapshot, where the
# exposition endpoint re-renders it with the node label
_device_memory_bytes = registry().gauge(
    "dlrover_tpu_device_memory_bytes",
    "per-device HBM from jax memory_stats() (kind: used | limit)",
    label_names=("device", "kind"),
)


try:
    import psutil
except ImportError:  # stats degrade, the agent must not
    psutil = None


def host_stats() -> tuple[float, int]:
    """(cpu_percent, used_memory_mb) for the whole host."""
    if psutil is None:
        return 0.0, 0
    try:
        cpu = psutil.cpu_percent(interval=None)
        mem = int(psutil.virtual_memory().used / (1 << 20))
        return cpu, mem
    except Exception:  # noqa: BLE001 - stats must never break the agent
        logger.exception("psutil host stats failed")
        return 0.0, 0


class ResourceMonitor:
    """Periodic host-stats reporter thread living in the agent."""

    def __init__(self, client, interval_s: float = 15.0,
                 tpu_chips: int = 0):
        self._client = client
        self._interval_s = interval_s
        self._tpu_chips = tpu_chips
        self._stopped = threading.Event()
        self._thread: threading.Thread | None = None

    def start(self) -> None:
        if psutil is None:
            logger.warning(
                "psutil unavailable; host resource monitoring disabled"
            )
            return
        self._thread = threading.Thread(
            target=self._loop, name="resource-monitor", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stopped.set()

    def _loop(self) -> None:
        # prime cpu_percent's interval-less mode (first call returns 0)
        host_stats()
        while not self._stopped.wait(self._interval_s):
            cpu, mem = host_stats()
            try:
                self._client.report_resource(
                    cpu_percent=cpu, used_memory_mb=mem,
                    tpu_chips=self._tpu_chips,
                )
            except (ConnectionError, RuntimeError, OSError) as e:
                logger.warning("resource report failed: %s", e)


def publish_device_memory() -> int:
    """Per-device HBM used/limit gauges + total used MB.

    Reads ``jax.local_devices()[i].memory_stats()`` — None on backends
    without it (CPU), so every field access is None-safe
    and a statless backend publishes nothing and returns 0. Must only be
    called from the process that owns the chips (the trainer)."""
    try:
        import jax

        total = 0
        for d in jax.local_devices():
            stats = d.memory_stats()
            if not stats:
                continue
            used = int(stats.get("bytes_in_use", 0) or 0)
            limit = int(stats.get("bytes_limit", 0) or 0)
            _device_memory_bytes.labels(str(d.id), "used").set(used)
            if limit > 0:
                _device_memory_bytes.labels(str(d.id), "limit").set(limit)
            total += used
        return total // (1 << 20)
    except Exception:  # noqa: BLE001
        return 0


def local_hbm_used_mb() -> int:
    """HBM bytes in use across this process's local devices (0 if the
    runtime doesn't expose memory_stats — e.g. CPU).
    Also refreshes the per-device ``dlrover_tpu_device_memory_bytes``
    gauges as a side effect."""
    return publish_device_memory()
