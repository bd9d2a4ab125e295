"""The device gate and the table of peaks. A run that finds no TPU, too few
chips, or a ``device_kind`` missing from ``peaks.json`` fails; nothing here
falls back to the CPU."""

from __future__ import annotations

import dataclasses
import os
import sys

from .spec import load_json


class NoChip(SystemExit):
    pass


@dataclasses.dataclass
class DeviceInfo:
    platform: str
    kind: str
    count: int  # devices JAX reports
    chips: int  # chips the cell uses
    bf16_flops_per_s: float
    hbm_bytes_per_s: float

    def public(self) -> dict:
        return {"platform": self.platform, "kind": self.kind,
                "count": self.count}


def peaks_for(bench_dir: str, kind: str) -> dict:
    table = load_json(os.path.join(bench_dir, "peaks.json"))["device_kinds"]
    if kind not in table:
        raise KeyError(f"device_kind {kind!r} is not in peaks.json "
                       f"(known: {sorted(table)})")
    return table[kind]


def gate(bench_dir: str, chips: int) -> DeviceInfo:
    import jax

    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        print(f"benchmark refused: platform is {d.platform!r}, not 'tpu'",
              file=sys.stderr)
        raise NoChip(2)
    if len(devices) < chips:
        print(f"benchmark refused: the cell needs {chips} chips, JAX reports "
              f"{len(devices)}", file=sys.stderr)
        raise NoChip(2)
    try:
        peak = peaks_for(bench_dir, d.device_kind)
    except KeyError as e:
        print(f"benchmark refused: {e}", file=sys.stderr)
        raise NoChip(2) from e
    return DeviceInfo(d.platform, d.device_kind, len(devices), chips,
                      float(peak["bf16_flops_per_s"]),
                      float(peak["hbm_bytes_per_s"]))


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the chips the cell uses."""
    import jax

    peak = 0
    for d in jax.devices()[:max(chips, 1)]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
