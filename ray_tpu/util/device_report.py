"""What this process's JAX runs on, as JAX reports it.

Results that depend on the device name it: ``chip_smoke.py`` fails a phase
whose worker does not report ``platform == "tpu"``, and ``LLMDeployment``
carries the report in ``get_stats()`` so a driver that never imports jax can
ask a replica where it runs.
"""

from __future__ import annotations

import os


def device_report() -> dict:
    import jax

    devices = jax.devices()

    def memory(d):
        stats = d.memory_stats() or {}  # None where the backend keeps none (CPU)
        return {
            "id": d.id,
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "bytes_limit": stats.get("bytes_limit"),
        }

    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "count": len(devices),
        "pid": os.getpid(),
        "memory": [memory(d) for d in jax.local_devices()],
    }
