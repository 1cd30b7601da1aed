"""Reduction of a ``jax.profiler`` trace to the benchmark's device numbers.

The traced run wraps the rounds it profiles in one host annotation,
``bench.window``; everything below is measured inside it:

  busy_s       the union of the intervals in which a device operation
               ran, averaged over the devices that ran any
  window_s     the annotation's length
  idle_share   1 - busy_s / window_s
  kernels      device seconds of the operations named as a kernel is
               (an operation's own name, without its ``%`` and number:
               a Pallas call is named after its kernel function)
  device_ops   device seconds per ``<program>/<operation>``, largest
               first; an operation nested in another (a loop's body) is
               counted in the outer one only
  idle_gaps    idle device seconds by what the host was doing: each gap
               is cut where host activities start and end, and each piece
               is named by the innermost activity covering it: a host event
               of the profiler (the benchmark's annotations, JAX's dispatch
               of a jitted function), or ``(no host activity)``

Devices are the ``/device:*`` planes: their operations are the events of
the line ``XLA Ops``, each in the program (``XLA Modules``) running at its
start.  A trace recorded on the CPU has no device plane; there the XLA
operations run on host threads, and the events that carry an ``hlo_op``
stat (with its ``hlo_module``) stand in for device operations, so the
reduction can be tested without a chip.
"""
from __future__ import annotations

import glob
import math
import os
import re
import warnings
from collections import defaultdict

import numpy as np

WINDOW = "bench.window"
NO_HOST = "(no host activity)"


def latest_trace(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir!r}")
    return max(paths, key=os.path.getmtime)


def _stats(ev) -> dict:
    with warnings.catch_warnings():  # the stats type warns on iteration
        warnings.simplefilter("ignore", DeprecationWarning)
        return {k: v for k, v in ev.stats}


def _short(name: str) -> str:
    """An operation's own name: ``%range_scan_pallas.1 = (s32[...]) ...``
    and ``range_scan_pallas.1`` both give ``range_scan_pallas``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def _module(name: str) -> str:
    """A program's name without its fingerprint: ``jit__v_split(97…)``."""
    return re.sub(r"\(\d+\)$", "", name)


def _events(profile):
    """``(devices, host)``.  ``devices`` maps each device to its
    operations, ``(short name, start_ns, end_ns, program)``; ``host`` is
    the ``(name, start_ns, end_ns)`` events of the thread that entered
    the ``bench.window`` annotation."""
    devices = {}
    host_lines = []
    cpu_ops = []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, _module(ev.name))
                          for ev in lines.get("XLA Modules", []))
            starts = np.asarray([m[0] for m in mods], np.float64)
            ops = []
            for ev in lines.get("XLA Ops", []):
                k = int(np.searchsorted(starts, ev.start_ns, side="right")) - 1
                prog = mods[k][2] if k >= 0 and mods[k][1] >= ev.start_ns else "?"
                ops.append((_short(ev.name), ev.start_ns, ev.start_ns + ev.duration_ns, prog))
            devices[plane.name] = ops
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = []
            for ev in line.events:
                st = _stats(ev)
                if "hlo_op" in st:
                    cpu_ops.append((_short(ev.name), ev.start_ns, ev.start_ns + ev.duration_ns,
                                    _module(str(st.get("hlo_module", "?")))))
                elif ev.duration_ns > 0:
                    events.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
            host_lines.append(events)
    devices = {d: ops for d, ops in devices.items() if ops}
    if not devices and cpu_ops:
        devices["/host:CPU"] = cpu_ops
    host = next((ev for ev in host_lines if any(n == WINDOW for n, _, _ in ev)), [])
    return devices, host


def _union(intervals):
    """Merged, sorted ``[(start, end)]`` of possibly overlapping intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def _clip(ops, t0, t1):
    return [
        (n, max(a, t0), min(b, t1), x) for n, a, b, x in ops if b > t0 and a < t1
    ]


def _namer(intervals):
    """Innermost interval covering a point, ``name_at(t)``.  Intervals
    of one thread nest, so the innermost cover is the latest-starting
    interval that has not ended yet."""
    items = sorted(intervals, key=lambda x: x[1])
    starts = np.asarray([a for _, a, _ in items], np.float64)

    def name_at(t):
        k = int(np.searchsorted(starts, t, side="right"))
        for j in range(k - 1, -1, -1):
            if items[j][2] >= t:
                return items[j][0]
        return NO_HOST

    return name_at


def reduce_trace(path: str, *, kernels: dict | None = None, top: int = 10) -> dict:
    """Reduce the trace at ``path`` (see the module docstring).
    ``kernels`` maps a metric key to a kernel's stable name."""
    from jax.profiler import ProfileData

    devices, host = _events(ProfileData.from_file(path))
    windows = [(a, b) for n, a, b in host if n == WINDOW]
    if not windows:
        raise ValueError(f"trace {path!r} has no {WINDOW!r} annotation")
    t0, t1 = windows[0]
    window_ns = t1 - t0
    clipped = {d: _clip(ops, t0, t1) for d, ops in devices.items()}
    clipped = {d: ops for d, ops in clipped.items() if ops}
    busy = {d: sum(b - a for a, b in _union((a, b) for _, a, b, _ in ops))
            for d, ops in clipped.items()}
    busy_ns = float(np.mean(list(busy.values()))) if busy else 0.0
    n_dev = max(1, len(clipped))

    per_op = defaultdict(float)
    kernel_ns = {k: 0.0 for k in (kernels or {})}
    for ops in clipped.values():
        end = -math.inf  # operations nest (a loop and its body): count the outer once
        for name, a, b, prog in sorted(ops, key=lambda o: (o[1], -o[2])):
            if a >= end:
                per_op[f"{prog}/{name}"] += b - a
                end = b
            for key, stable in (kernels or {}).items():
                if name == stable:
                    kernel_ns[key] += b - a

    # idle gaps of the first device with work, named by host activity
    host_iv = [(n, a, b) for n, a, b in host if a < t1 and b > t0 and n != WINDOW]
    name_at = _namer(host_iv)
    edges = np.unique(np.asarray([x for _, a, b in host_iv for x in (a, b)], np.float64))
    idle = defaultdict(float)

    def charge(a, b):
        """Split the gap [a, b) at host-activity edges; name each piece."""
        cuts = edges[np.searchsorted(edges, a, "right"):np.searchsorted(edges, b, "left")].tolist()
        for lo, hi in zip([a] + cuts, cuts + [b]):
            idle[name_at((lo + hi) / 2)] += hi - lo

    busy_first = _union((a, b) for _, a, b, _ in clipped[sorted(clipped)[0]]) if clipped else []
    cursor = t0
    for a, b in busy_first + [[t1, t1]]:
        if a > cursor:
            charge(cursor, a)
        cursor = max(cursor, b)

    def top_list(d, scale):
        return [[n, v * scale] for n, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_ns / 1e9,
        "idle_share": 1.0 - busy_ns / window_ns if window_ns > 0 else None,
        "devices": len(clipped),
        "kernels_s": {k: v / 1e9 / n_dev for k, v in kernel_ns.items()},
        "device_ops": top_list(per_op, 1e-9 / n_dev),
        "idle_gaps": top_list(idle, 1e-9),
    }
