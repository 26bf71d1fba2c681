"""Profiler trace of a run's window, and its reduction to device numbers.

The capture writes the JAX profiler's ``.xplane.pb``; :func:`parse` turns
it into plain event records, and everything after that works on those
records alone, so a small recorded trace (``bench/tests/data``) checks the
reduction without a chip.

Device planes are ``/device:TPU:<i>``. On them, the ``XLA Modules`` line
holds one event per program execution (``jit_<function>(<id>)``) and the
``XLA Ops`` line one event per operation, Pallas kernels included.
"""
from __future__ import annotations

import dataclasses
import glob
import heapq
import re
import time
from pathlib import Path

import jax

MODULES = "XLA Modules"
# Pallas kernel -> substrings of its op names in a TPU trace
KERNEL_OPS = {
    "flash_decode": ("flash_decode",),
}
OPS = "XLA Ops"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def capture(fn, log_dir: Path):
    """Run ``fn()`` under the profiler; returns (its result, window
    seconds by the host clock, path of the trace file)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # host spans from JAX itself only
    opts.host_tracer_level = 2
    log_dir.mkdir(parents=True, exist_ok=True)
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    t0 = time.perf_counter()
    try:
        out = fn()
    finally:
        window_s = time.perf_counter() - t0
        jax.profiler.stop_trace()
    files = sorted(glob.glob(str(log_dir / "plugins" / "profile" / "*" /
                                 "*.xplane.pb")))
    return out, window_s, Path(files[-1])


def parse(path: Path) -> list[Event]:
    """Every event on every device plane and host thread."""
    from jax.profiler import ProfileData

    events = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not (plane.name.startswith("/device:")
                or plane.name.startswith("/host:CPU")):
            continue
        for line in plane.lines:
            for ev in line.events:
                events.append(Event(plane.name, line.name, ev.name,
                                    float(ev.start_ns),
                                    float(ev.duration_ns)))
    return events


def base_name(name: str) -> str:
    """``jit_decode_step(123)`` -> ``jit_decode_step`` (a numeric id in
    parentheses goes; ``PjitFunction(decode_step)`` stays)."""
    return re.sub(r"\(\d+\)$", "", name)


def device_planes(events: list[Event]) -> list[str]:
    return sorted({e.plane for e in events if e.plane.startswith("/device:")
                   and e.line in (OPS, MODULES)})


def union_ns(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merge [start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _busy_line(events: list[Event], plane: str) -> list[Event]:
    ops = [e for e in events if e.plane == plane and e.line == OPS]
    return ops or [e for e in events if e.plane == plane and e.line == MODULES]


def busy_s(events: list[Event]) -> float:
    """Seconds in which an operation ran, averaged over the device planes."""
    planes = device_planes(events)
    if not planes:
        return 0.0
    total = 0.0
    for p in planes:
        merged = union_ns([(e.start_ns, e.end_ns) for e in _busy_line(events, p)])
        total += sum(e - s for s, e in merged)
    return total / len(planes) / 1e9


def module_events(events: list[Event], module: str) -> list[Event]:
    """Executions of the program ``module`` (e.g. ``jit_decode_step``) on
    the first device plane."""
    planes = device_planes(events)
    if not planes:
        return []
    return [e for e in events if e.plane == planes[0] and e.line == MODULES
            and base_name(e.name) == module]


def op_events(events: list[Event], match) -> list[Event]:
    """Operations on the first device plane whose name satisfies
    ``match(name)``."""
    planes = device_planes(events)
    if not planes:
        return []
    return [e for e in events if e.plane == planes[0] and e.line == OPS
            and match(e.name)]


def kernel_match(kernel: str):
    """Matcher for a Pallas kernel's op events: the op name holds the
    kernel's name (``KERNEL_OPS`` maps it to the names the TPU trace
    gives its custom calls)."""
    names = KERNEL_OPS.get(kernel, (kernel,))
    return lambda name: any(n in name for n in names)


def op_name(name: str) -> str:
    """An op event's name without its HLO text:
    ``%fusion.7 = f32[...] fusion(...)`` -> ``%fusion.7``."""
    return name.split(" = ", 1)[0]


def self_times(events: list[Event]) -> list[tuple[Event, float]]:
    """Each event with its self time: its duration less that of the
    events nested in it (a ``while`` op holds the ops of its body)."""
    order = sorted(events, key=lambda e: (e.start_ns, -e.dur_ns))
    selfs = {id(e): e.dur_ns for e in order}
    stack: list[Event] = []
    for e in order:
        while stack and stack[-1].end_ns <= e.start_ns:
            stack.pop()
        if stack and e.end_ns <= stack[-1].end_ns:
            selfs[id(stack[-1])] -= e.dur_ns
        stack.append(e)
    return [(e, selfs[id(e)]) for e in order]


def top_ops(events: list[Event], n: int = 10) -> list[list]:
    """The ``n`` device operations (first plane) that took most self
    time, summed by op name, in seconds."""
    planes = device_planes(events)
    if not planes:
        return []
    acc: dict[str, float] = {}
    for e, t in self_times(_busy_line(events, planes[0])):
        acc[op_name(e.name)] = acc.get(op_name(e.name), 0.0) + t / 1e9
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(events: list[Event], n: int = 10,
              min_gap_ns: float = 20_000.0) -> list[list]:
    """Device idle gaps on the first plane, each labelled by the shortest
    host span that covers its midpoint (``no host span`` where none does),
    summed by label; the ``n`` largest labels."""
    planes = device_planes(events)
    if not planes:
        return []
    merged = union_ns([(e.start_ns, e.end_ns)
                       for e in _busy_line(events, planes[0])])
    gaps = [(e0, s1) for (_, e0), (s1, _) in zip(merged, merged[1:])
            if s1 - e0 >= min_gap_ns]
    host = sorted((e for e in events if e.plane.startswith("/host:")
                   and e.dur_ns > 0), key=lambda e: e.start_ns)
    acc: dict[str, float] = {}
    active: list = []  # heap of (duration, start, index) of open spans
    i = 0
    for e0, s1 in gaps:  # in time order
        mid = 0.5 * (e0 + s1)
        while i < len(host) and host[i].start_ns <= mid:
            heapq.heappush(active, (host[i].dur_ns, host[i].start_ns, i))
            i += 1
        while active and host[active[0][2]].end_ns < mid:
            heapq.heappop(active)
        label = base_name(host[active[0][2]].name) if active else "no host span"
        acc[label] = acc.get(label, 0.0) + (s1 - e0) / 1e9
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]
