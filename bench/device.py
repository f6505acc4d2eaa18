"""The accelerator a run measures, and its published peaks.

A run measures a TPU or nothing: with no accelerator, too few chips, or
a device kind missing from ``peaks.json`` it stops before it prints a
result.  There is no fallback to the CPU.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


class NoAccelerator(RuntimeError):
    pass


def peaks_for(kind: str, path: Path = PEAKS) -> dict:
    table = json.loads(path.read_text())["devices"]
    if kind not in table:
        raise NoAccelerator(f"no peaks for device kind {kind!r} in "
                            f"{path.name}; known: {sorted(table)}")
    return table[kind]


def accelerator(chips: int, devices=None) -> tuple[list, dict]:
    """The first ``chips`` TPU devices and their peaks."""
    if devices is None:
        import jax

        devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX's first device is {dev.platform} "
                            f"({dev.device_kind}); the benchmark measures "
                            f"the chip only")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX sees "
                            f"{len(devices)}")
    return list(devices[:chips]), peaks_for(dev.device_kind)

