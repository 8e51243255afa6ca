"""What several metric readers share: a reader that finds nothing to read
returns None, and the harness leaves that metric out of the line."""

from __future__ import annotations

from benchmarks.harness.client import median


def device0(result: dict):
    """The reduced trace of the first device, or None where no device was traced."""
    trace = result.get("trace")
    return trace["devices"][0] if trace and trace.get("devices") else None


def program_median_ms(result: dict, key: str):
    """Median device time of one program's executions in the traced slice."""
    dev = device0(result)
    runs = dev["programs"].get(key) if dev else None
    return median(runs) * 1000.0 if runs else None


def gap_median_ms(result: dict, label=None):
    """Median idle gap between successive programs (of one kind of pair)."""
    dev = device0(result)
    gaps = [g for _, g, what in dev["gaps"] if label in (None, what)] if dev else []
    return median(gaps) * 1000.0 if gaps else None


def idle_pct(result: dict):
    """1 - busy union / traced slice, both means over the cell's devices."""
    t = result.get("trace")
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"]) if t and t["window_s"] else None
