"""From a profiler trace to the benchmark's device numbers.

``extract`` keeps what the reduction needs from a JAX profiler trace: every
device plane's op intervals and the benchmark's own ``TraceAnnotation``
spans (names starting ``bench/``) on the host threads.  ``reduce`` gives

* ``busy_s``: the union of the device's op intervals inside the traced
  window, averaged over the devices;
* ``window_s`` and the idle share ``1 - busy_s / window_s``;
* ``device_ops``: device seconds per HLO opcode (with a fusion's kind, as
  ``fusion:kOutput``), most first;
* ``idle_gaps``: seconds in which the first device ran nothing, by what the
  host was doing then (the innermost benchmark span on each host thread at
  the gap's middle, joined with ``+``), most first.

Times are in nanoseconds from the trace's own start, as the profiler gives
them for host and device planes alike.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from collections import defaultdict
from typing import Optional

PREFIX = "bench/"
WINDOW = "window"
TOP = 10
_OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")
_KIND = re.compile(r"kind=(k\w+)")


def opcode(hlo: str) -> str:
    """``%fusion.3 = f32[8]{0} fusion(...), kind=kLoop`` -> ``fusion:kLoop``."""
    rhs = hlo.split(" = ", 1)[-1]
    m = _OPCODE.search(" " + rhs)
    if m is None:
        return rhs.split("(", 1)[0] or hlo
    kind = _KIND.search(rhs)
    return f"{m.group(1)}:{kind.group(1)}" if kind else m.group(1)


def extract_profile(profile) -> dict:
    """``profile`` is a ``jax.profiler.ProfileData``."""
    devices: dict[str, list] = {}
    host: list = []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            ops = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend([e.start_ns, e.start_ns + e.duration_ns, e.name]
                               for e in line.events)
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    [e.start_ns, e.start_ns + e.duration_ns,
                     e.name[len(PREFIX):], line.name]
                    for e in line.events if e.name.startswith(PREFIX))
    return {"devices": devices, "host": host}


def extract_dir(log_dir: str) -> dict:
    """Extract the one ``.xplane.pb`` that ``jax.profiler.trace`` wrote."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {len(paths)}")
    return extract_profile(ProfileData.from_file(paths[0]))


def load(path: str) -> dict:
    """An extracted trace kept as gzip JSON (the tests' recorded trace)."""
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _label(host: list, t: float) -> str:
    """Innermost benchmark span on each host thread at time ``t``."""
    inner: dict[str, tuple[float, str]] = {}
    for s, e, name, thread in host:
        if name != WINDOW and s <= t < e:
            if thread not in inner or s > inner[thread][0]:
                inner[thread] = (s, name)
    return "+".join(sorted({name for _, name in inner.values()})) or "none"


def reduce(extracted: dict) -> Optional[dict]:
    """The device numbers of the traced window; None when the trace holds no
    device plane (nothing to read)."""
    devices = extracted["devices"]
    if not devices:
        return None
    host = extracted["host"]
    windows = [(s, e) for s, e, name, _ in host if name == WINDOW]
    if windows:
        lo, hi = windows[0]
    else:
        lo = min(op[0] for ops in devices.values() for op in ops)
        hi = max(op[1] for ops in devices.values() for op in ops)
    busy = []
    per_op: dict[str, float] = defaultdict(float)
    for ops in devices.values():
        clipped = _clip([(s, e) for s, e, _ in ops], lo, hi)
        busy.append(sum(e - s for s, e in _union(clipped)))
        for s, e, name in ops:
            if e > lo and s < hi:
                per_op[opcode(name)] += (min(e, hi) - max(s, lo)) / len(devices)
    first = sorted(devices)[0]
    union = _union(_clip([(s, e) for s, e, _ in devices[first]], lo, hi))
    gaps, prev = [], lo
    for s, e in union + [(hi, hi)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    per_label: dict[str, float] = defaultdict(float)
    for s, e in gaps:
        per_label[_label(host, (s + e) / 2)] += e - s
    window_ns = hi - lo
    busy_ns = sum(busy) / len(busy)

    def top(d):
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {"busy_s": busy_ns / 1e9, "window_s": window_ns / 1e9,
            "idle_share": 1.0 - busy_ns / window_ns if window_ns else None,
            "n_devices": len(devices), "device_ops": top(per_op),
            "idle_gaps": top(per_label)}
