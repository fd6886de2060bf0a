"""Find the chips a cell asks for, or fail: there is no CPU fallback."""

from __future__ import annotations

import json
import os
from typing import Dict, List


class DeviceError(RuntimeError):
    """No accelerator, too few chips, or a chip whose peaks are not known."""


def load_peaks() -> Dict[str, dict]:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        return {k: v for k, v in json.load(f).items() if not k.startswith("_")}


def find_chips(chips: int, *, rehearsal: bool = False):
    """(devices to use, peaks of one chip or None, the ``device`` object of
    the result line). ``rehearsal`` lets a CPU through, for the selftest's
    tiny preset: its result line names the device ``cpu`` and carries
    counts only."""
    import jax

    devices: List = jax.devices()
    dev = devices[0]
    info = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}
    if rehearsal:
        if len(devices) < chips:
            raise DeviceError(f"rehearsal needs {chips} (virtual) devices, JAX has {len(devices)}")
        return devices[:chips], None, info
    if dev.platform == "cpu":
        raise DeviceError("JAX found no accelerator; the benchmark measures on the chip only")
    if len(devices) < chips:
        raise DeviceError(f"the cell needs {chips} chips, JAX has {len(devices)}")
    peaks = load_peaks().get(dev.device_kind)
    if peaks is None:
        raise DeviceError(
            f"device_kind {dev.device_kind!r} is not in benchmark/harness/peaks.json; "
            "add its published peaks before measuring on it"
        )
    return devices[:chips], peaks, info


def memory_by_chip(devices) -> List[dict]:
    """Per device, bytes in use now and at the peak so far (a fact line of
    the run: which chip is the fullest, and when)."""
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append({"in_use": int(stats.get("bytes_in_use", 0)),
                    "peak": int(stats.get("peak_bytes_in_use", 0))})
    return out


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices`` (0 where the backend
    keeps no count, as the CPU does)."""
    return max((m["peak"] for m in memory_by_chip(devices)), default=0)
