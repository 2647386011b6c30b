"""Reduce a profiler trace (``*.xplane.pb``) to what the per-layer readers need.

A TPU trace holds one plane per chip, named ``/device:TPU:<i>``, whose line
``XLA Ops`` has one event per operation the chip's TensorCore ran, named by
its HLO instruction (``%gram_padded.1 = (...) custom-call(...)``), with its
start and duration in nanoseconds; the line ``Async XLA Ops`` holds the
asynchronous copies and collectives.  The host plane ``/host:CPU`` holds
the Python thread's events (``PjitFunction(...)`` dispatches) and the
benchmark's own annotations, on the same clock.  The benchmark opens one
host annotation, `WINDOW`, around its measured window; every number here
is clipped to it, and the host line that holds it is the Python thread.

Off a TPU (the CPU rehearsal) there is no device plane; `load(...,
host_as_device=True)` then reads the host events that carry an ``hlo_op``
stat as the operations of one pseudo-device, so the readers can be
exercised.  No number read that way is a device metric.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW = "bench_window"
DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute", re.IGNORECASE)


@dataclasses.dataclass
class Op:
    name: str
    start: float      # ns
    end: float        # ns
    text: str         # name plus every string stat, for matching


@dataclasses.dataclass
class Trace:
    devices: dict[str, list[Op]]          # plane name -> ops, by start
    host: list[Op]                        # host events of the Python thread
    window: tuple[float, float]           # ns
    async_ops: dict[str, list[Op]] = dataclasses.field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def find_file(log_dir: str) -> str | None:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return files[-1] if files else None


def _op(ev) -> Op:
    texts = [ev.name]
    for _, value in ev.stats:
        if isinstance(value, str):
            texts.append(value)
    return Op(ev.name, float(ev.start_ns), float(ev.end_ns), " ".join(texts))


def load(path: str, *, host_as_device: bool = False) -> Trace:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices: dict[str, list[Op]] = {}
    async_ops: dict[str, list[Op]] = {}
    host_lines: list[list[Op]] = []
    pseudo: list[Op] = []
    window = None
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name in (OPS_LINE, ASYNC_LINE):
                    ops = sorted((_op(e) for e in line.events),
                                 key=lambda o: o.start)
                    (devices if line.name == OPS_LINE
                     else async_ops)[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = []
                for ev in line.events:
                    if ev.name == WINDOW:
                        window = (float(ev.start_ns), float(ev.end_ns))
                        host_lines.insert(0, events)
                    events.append(_op(ev))
                    if host_as_device and any(k == "hlo_op"
                                              for k, _ in ev.stats):
                        pseudo.append(events[-1])
    if not devices and host_as_device and pseudo:
        devices["/host:CPU"] = sorted(pseudo, key=lambda o: o.start)
    if window is None:
        ops = [o for v in devices.values() for o in v]
        window = ((min(o.start for o in ops), max(o.end for o in ops))
                  if ops else (0.0, 0.0))
    host = sorted(host_lines[0], key=lambda o: o.start) if host_lines else []
    return Trace(devices=devices, host=host, window=window,
                 async_ops=async_ops)


def short_name(name: str) -> str:
    """``%gram_padded.1 = (...) custom-call(...)`` -> ``gram_padded.1``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _clip(ops, lo: float, hi: float):
    for o in ops:
        s, e = max(o.start, lo), min(o.end, hi)
        if e > s:
            yield s, e


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def busy_s(trace: Trace, plane: str) -> float:
    """Seconds in the window during which any operation ran on ``plane``."""
    lo, hi = trace.window
    return _length(union(_clip(trace.devices[plane], lo, hi))) * 1e-9


def mean_busy_s(trace: Trace) -> float:
    if not trace.devices:
        return 0.0
    return sum(busy_s(trace, p) for p in trace.devices) / len(trace.devices)


def op_seconds(trace: Trace, pattern: str) -> dict[str, float]:
    """Per device: summed in-window duration of the ops whose name or
    string stats match ``pattern`` (a regular expression)."""
    rx = re.compile(pattern)
    lo, hi = trace.window
    return {p: sum(e - s for s, e in _clip(
                [o for o in ops if rx.search(o.text)], lo, hi)) * 1e-9
            for p, ops in trace.devices.items()}


def exposed_collective_s(trace: Trace, plane: str) -> float:
    """Seconds of collective ops on ``plane`` during which no other op ran
    there: the part of the exchange that compute did not hide."""
    lo, hi = trace.window
    ops = trace.devices[plane]
    coll = union(_clip([o for o in ops + trace.async_ops.get(plane, [])
                        if COLLECTIVE.search(short_name(o.name))], lo, hi))
    comp = union(_clip([o for o in ops
                        if not COLLECTIVE.search(short_name(o.name))],
                       lo, hi))
    covered, j = 0.0, 0
    for s, e in coll:
        while j < len(comp) and comp[j][1] <= s:
            j += 1
        k = j
        while k < len(comp) and comp[k][0] < e:
            covered += min(e, comp[k][1]) - max(s, comp[k][0])
            k += 1
    return (_length(coll) - covered) * 1e-9


def top_ops(trace: Trace, k: int = 10) -> list[list]:
    """The ``k`` op names with the most in-window device time, in seconds
    averaged over the devices."""
    lo, hi = trace.window
    tot: dict[str, float] = {}
    for ops in trace.devices.values():
        for o in ops:
            s, e = max(o.start, lo), min(o.end, hi)
            if e > s:
                key = short_name(o.name)
                tot[key] = tot.get(key, 0.0) + (e - s) * 1e-9
    n = max(len(trace.devices), 1)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, sec / n] for name, sec in best]


def idle_gaps(trace: Trace, k: int = 10) -> list[list]:
    """The ``k`` longest in-window gaps on the first device, each named by
    the innermost host event of the Python thread at the gap's midpoint
    ("host idle" where there is none)."""
    if not trace.devices:
        return []
    lo, hi = trace.window
    plane = sorted(trace.devices)[0]
    busy = union(_clip(trace.devices[plane], lo, hi))
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:k]
    out = []
    for s, e in gaps:
        mid = 0.5 * (s + e)
        inner = [o for o in trace.host
                 if o.start <= mid <= o.end and o.name != WINDOW]
        label = (min(inner, key=lambda o: o.end - o.start).name
                 if inner else "host idle")
        out.append([label, (e - s) * 1e-9])
    return out
