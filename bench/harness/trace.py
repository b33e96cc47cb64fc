"""Reduce a profiler trace (``.xplane.pb``) to device time.

`load` reads the trace with `jax.profiler.ProfileData`; nothing else of
JAX is touched, and nothing is imported until it is called, so this
module loads no accelerator library.  What it keeps:

- every device operation (the ``XLA Ops`` line of each ``/device:``
  plane): its HLO text, start and duration, in nanoseconds from the
  start of the trace;
- every host event (the ``/host:CPU`` plane), to say what the host was
  doing while a device sat idle;
- the measured window: the host span named `WINDOW`, which the
  benchmark opens around its window, or else the whole trace.  Busy and
  idle time are counted inside it, so starting and stopping the
  profiler are not counted as idle.

`busy_intervals` merges one device's operations into the intervals in
which something ran; busy time is their total length and idle time the
rest of the window.  `breakdown` gives the operations that took most
time and the longest idle gaps, each gap named by the host event that
overlaps it most (the most specific one where several cover it).
"""
from __future__ import annotations

import re
from typing import Dict, List, NamedTuple, Optional, Tuple

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")
CONTROL = ("while", "conditional", "call")   # spans that hold other ops
WINDOW = "bench.window"
_SHAPE_ROWS = re.compile(r"\(f32\[(\d+),(\d+)\]")


class Op(NamedTuple):
    name: str          # HLO text: "%name = shape opcode(operands), ..."
    start_ns: float
    dur_ns: float


class Trace(NamedTuple):
    start_ns: float                                 # the window
    window_ns: float
    devices: Dict[str, List[Op]]                    # plane -> its ops
    host: List[Tuple[str, float, float]]            # (name, start, dur)
    epoch_ns: float = 0.0     # wall-clock time of the trace's zero


def with_host_spans(trace: Trace, spans) -> Trace:
    """``trace`` with host spans ``(name, start, end)`` given in seconds
    since the epoch (the compile clock's) added to its host events."""
    extra = [(name, s * 1e9 - trace.epoch_ns, (e - s) * 1e9)
             for name, s, e in spans]
    return trace._replace(host=trace.host + extra)


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Op]] = {}
    host: List[Tuple[str, float, float]] = []
    profile_ns = epoch_ns = None
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            ops = [Op(e.name, float(e.start_ns), float(e.duration_ns))
                   for line in plane.lines if line.name == "XLA Ops"
                   for e in line.events]
            if ops:
                devices[plane.name] = sorted(ops, key=lambda o: o.start_ns)
        elif plane.name == "/host:CPU":
            host.extend((e.name, float(e.start_ns), float(e.duration_ns))
                        for line in plane.lines for e in line.events)
        elif plane.name == "Task Environment":
            st = dict(plane.stats)
            epoch_ns = float(st["profile_start_time"])
            profile_ns = float(st["profile_stop_time"]) - epoch_ns
    if profile_ns is None:
        raise ValueError(f"{path}: no profile start and stop time")
    marks = [(s, d) for name, s, d in host if name == WINDOW]
    start, length = marks[0] if marks else (0.0, profile_ns)
    return Trace(start, length, devices, host, epoch_ns)


def opcode(name: str) -> str:
    """The HLO opcode of an op's text: ``custom-call``, ``fusion``,
    ``all-gather-start`` ..."""
    rhs = name.split(" = ", 1)[-1]
    if rhs.startswith("("):                 # tuple shape
        depth = 0
        for i, ch in enumerate(rhs):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rhs = rhs[i + 1:]
                break
    else:
        rhs = rhs.split(" ", 1)[-1]
    return rhs.strip().split("(", 1)[0]


def short_name(name: str) -> str:
    """``%fusion.3 = ...`` -> ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%")


def is_collective(op: Op) -> bool:
    code = opcode(op.name)
    return any(code == c or code.startswith(c + "-") for c in COLLECTIVES)


def operand_rows(op: Op) -> Optional[Tuple[int, int]]:
    """``(rows, lanes)`` of an op's first operand, where that is a 2-D
    f32 array."""
    at = op.name.find(" " + opcode(op.name) + "(")
    m = _SHAPE_ROWS.match(op.name, at + len(opcode(op.name)) + 1) \
        if at >= 0 else None
    return (int(m.group(1)), int(m.group(2))) if m else None


def busy_intervals(ops: List[Op], lo: float = float("-inf"),
                   hi: float = float("inf")) -> List[Tuple[float, float]]:
    """Merged ``(start, end)`` intervals in which any op ran, clipped to
    ``[lo, hi]``."""
    out: List[Tuple[float, float]] = []
    for op in sorted(ops, key=lambda o: o.start_ns):
        s = max(op.start_ns, lo)
        e = min(op.start_ns + op.dur_ns, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _window(trace: Trace) -> Tuple[float, float]:
    return trace.start_ns, trace.start_ns + trace.window_ns


def mean_busy_s(trace: Trace) -> float:
    """Busy seconds inside the window, averaged over the devices that
    ran anything."""
    if not trace.devices:
        return 0.0
    lo, hi = _window(trace)
    return sum(e - s for ops in trace.devices.values()
               for s, e in busy_intervals(ops, lo, hi)) \
        / len(trace.devices) / 1e9


def idle_gaps(trace: Trace) -> List[Tuple[float, float]]:
    """Every ``(start, end)`` of each device's idle time inside the
    window, longest first."""
    lo, hi = _window(trace)
    gaps = []
    for ops in trace.devices.values():
        t = lo
        for s, e in busy_intervals(ops, lo, hi):
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if hi > t:
            gaps.append((t, hi))
    return sorted(gaps, key=lambda g: g[0] - g[1])


def host_activity(trace: Trace, start: float, end: float) -> str:
    """The host event that overlaps ``[start, end)`` most; among those
    that overlap equally, the shortest."""
    best, key = "idle (no host event)", (0.0, 0.0)
    for name, s, d in trace.host:
        ov = min(end, s + d) - max(start, s)
        if ov > 0 and (ov, -d) > key:
            best, key = name, (ov, -d)
    return best


def breakdown(trace: Trace, top: int = 10) -> dict:
    """``device_ops``: the ops that took most device time, summed by
    name over the chips and averaged per chip (control flow, which
    holds other ops, left out); ``idle_gaps``: the longest idle gaps,
    named by what the host was doing."""
    per: Dict[str, float] = {}
    for ops in trace.devices.values():
        for op in ops:
            if opcode(op.name) in CONTROL:
                continue
            k = short_name(op.name)
            per[k] = per.get(k, 0.0) + op.dur_ns
    n = max(len(trace.devices), 1)
    ops_top = sorted(per.items(), key=lambda kv: -kv[1])[:top]
    gaps = idle_gaps(trace)[:top]
    return {
        "device_ops": [[k, v / n / 1e9] for k, v in ops_top],
        "idle_gaps": [[host_activity(trace, s, e), (e - s) / 1e9]
                      for s, e in gaps],
    }


def idle_percent(trace: Trace):
    """Share of the window in which no op ran, in %, averaged over the
    devices that ran anything; None where none did."""
    if not trace.devices or trace.window_ns <= 0:
        return None
    return 100.0 * (1.0 - mean_busy_s(trace) * 1e9 / trace.window_ns)
