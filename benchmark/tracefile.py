"""Reads a JAX profiler trace (``*.xplane.pb``) with nothing but JAX:
which planes and lines it holds, and the device operations as
``(name, start seconds, duration seconds)``."""

from __future__ import annotations

import glob
import os


def load(trace_dir: str):
    from jax.profiler import ProfileData

    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    return ProfileData.from_file(sorted(found)[-1])


def describe(data) -> list:
    """One line per plane and line, with its number of events."""
    out = []
    for plane in data.planes:
        for line in plane.lines:
            out.append(f"{plane.name} | {line.name} | "
                       f"{sum(1 for _ in line.events)} events")
    return out


def device_ops(data, plane_prefix: str, op_lines: list) -> dict:
    """plane name -> [(name, start_s, duration_s)] over the lines named
    ``op_lines`` of every plane whose name starts with ``plane_prefix``."""
    out: dict = {}
    for plane in data.planes:
        if not plane.name.startswith(plane_prefix):
            continue
        ops = out.setdefault(plane.name, [])
        for line in plane.lines:
            if line.name in op_lines:
                ops.extend((ev.name, ev.start_ns * 1e-9,
                            ev.duration_ns * 1e-9) for ev in line.events)
    return out
