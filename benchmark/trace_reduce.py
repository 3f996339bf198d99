"""From a profiler trace to device busy time, idle share and time per
operation.  The one reduction every PR uses, checked on a recorded trace in
tests/benchmark/test_benchmark_trace_reduce.py.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
A device is a plane named ``/device:TPU:<n>``; the line ``XLA Ops`` of that
plane holds one event for each operation the device ran, with a start and a
duration in nanoseconds (a ``while`` holds the operations of its body, which
overlap it).  Reduction, per device:

  window   the middle of the span from the first operation's start to the
           last one's end: ``TRIM`` of the span is cut from each side, so
           that the profiler's own start and stop, which stall the host, are
           not read as the program's idle time
  busy     the union of the operations' intervals, clipped to the window:
           overlapping events count once
  ops      each operation's self time in the window: its duration less what
           the operations nested inside it cover, summed by name
  modules  the line ``XLA Modules`` holds one event for each run of a whole
           compiled program, from its first operation to its last.  Every
           event of the trace is taken, untrimmed (an event is a whole run
           or it is not there): name, number of runs, seconds in all

``busy_s`` and ``window_s`` are averaged over the devices.
"""

from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
#: lines of a device plane that do not hold operations (whole programs,
#: steps, host-side markers): never counted as busy
NOT_OPS = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops",
           "Framework Name Scope", "Source code")
TRIM = 0.1


def find_xplane(trace_dir: str):
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def read_xplane(path: str) -> list:
    """Device events of a trace file as plain rows
    ``[plane, line, name, start_ns, duration_ns]``."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    rows = []
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                rows.append([plane.name, line.name, ev.name,
                             float(ev.start_ns), float(ev.duration_ns)])
    return rows


def reduce_xplane(path: str) -> dict:
    return reduce_rows(read_xplane(path))


def _op_events(rows):
    """plane -> [(start, end, name)] of the lines that hold operations."""
    by_plane = {}
    for plane, line, name, start, dur in rows:
        if not plane.startswith(DEVICE_PREFIX):
            continue
        by_plane.setdefault(plane, {}).setdefault(line, []).append(
            (start, start + dur, name))
    out = {}
    for plane, lines in by_plane.items():
        if OP_LINE in lines:
            evs = lines[OP_LINE]
        else:
            evs = [e for ln, es in lines.items() if ln not in NOT_OPS
                   for e in es]
        evs = [e for e in evs if e[1] > e[0]]
        if evs:
            out[plane] = sorted(evs, key=lambda e: (e[0], -e[1]))
    return out


def _union(intervals) -> float:
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _self_times(evs, lo, hi) -> dict:
    """name -> self nanoseconds inside [lo, hi].  ``evs`` is sorted by start,
    longer first; an event that starts inside another and ends inside it is
    its child."""
    out = {}
    stack = []   # [start, end, name, covered-by-children]

    def close(item):
        s, e, name, covered = item
        out[name] = out.get(name, 0.0) + max(e - s - covered, 0.0)

    for s, e, name in evs:
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        while stack and s >= stack[-1][1]:
            close(stack.pop())
        if stack:
            # only what lies inside the parent is taken from its self time
            stack[-1][3] += min(e, stack[-1][1]) - s
        stack.append([s, e, name, 0.0])
    while stack:
        close(stack.pop())
    return out


def _modules(rows) -> list:
    """[[name, runs, seconds]], most time first: the whole programs the
    devices ran, runs and seconds averaged over the devices.  A program's
    name is the trace's without the fingerprint it puts in brackets."""
    planes, out = set(), {}
    for plane, line, name, _start, dur in rows:
        if plane.startswith(DEVICE_PREFIX) and line == MODULE_LINE and dur > 0:
            planes.add(plane)
            item = out.setdefault(name.split("(")[0], [0, 0.0])
            item[0] += 1
            item[1] += dur
    n = max(len(planes), 1)
    return sorted(([name, runs / n, ns / n / 1e9]
                   for name, (runs, ns) in out.items()), key=lambda x: -x[2])


def reduce_rows(rows, trim: float = TRIM) -> dict:
    """The reduction above, from rows as ``read_xplane`` gives them.
    Returns seconds: ``window_s``, ``busy_s``, ``idle_share``, ``devices``
    and ``ops`` as ``[[name, seconds], ...]``, most time first (summed over
    devices, divided by their number); ``kinds`` is the same summed by the
    operation's name without its number (``fusion``, ``copy-start``, ...).  None where no device ran anything."""
    planes = _op_events(rows)
    if not planes:
        return None
    windows, busys, ops = [], [], {}
    for evs in planes.values():
        first = min(e[0] for e in evs)
        last = max(e[1] for e in evs)
        lo = first + trim * (last - first)
        hi = last - trim * (last - first)
        clipped = [(max(s, lo), min(e, hi)) for s, e, _n in evs
                   if min(e, hi) > max(s, lo)]
        windows.append(hi - lo)
        busys.append(_union(clipped))
        for name, ns in _self_times(evs, lo, hi).items():
            ops[name] = ops.get(name, 0.0) + ns
    n = len(planes)
    window_s = sum(windows) / n / 1e9
    busy_s = sum(busys) / n / 1e9
    stems = {}
    for name, ns in ops.items():
        stem = name.split(" = ")[0].lstrip("%").rstrip("0123456789.-")
        stems[stem] = stems.get(stem, 0.0) + ns
    return {"devices": n, "window_s": window_s, "busy_s": busy_s,
            "kinds": sorted(([k, ns / n / 1e9] for k, ns in stems.items()),
                            key=lambda x: -x[1]),
            "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
            "modules": _modules(rows),
            "ops": sorted(([name, ns / n / 1e9] for name, ns in ops.items()),
                          key=lambda x: -x[1])}
