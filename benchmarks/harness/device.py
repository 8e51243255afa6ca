"""The ``device`` object of a result line, from inside the process that holds
the chips: as jax reports it, with the peak on the fullest chip."""

from __future__ import annotations


def device_line(program_bytes: int = 0) -> dict:
    """``program_bytes``: the footprint ``compiled.memory_analysis()`` gives for
    the largest program; ``peak_bytes_in_use`` misses a compiled step's
    temporaries (PERF.md, PR 21), so the larger of the two is the peak."""
    import jax

    devices = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.local_devices()]
    if any(p is None for p in peaks):
        if devices[0].platform == "tpu":
            raise RuntimeError("the TPU backend reports no peak_bytes_in_use")
        import resource  # a CPU stand-in keeps no device statistics: the process's own peak

        peaks = [resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024]
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(max(max(peaks), program_bytes)),
    }
