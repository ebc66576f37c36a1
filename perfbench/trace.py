"""Profiler trace of the measured window, and its reduction to numbers.

:func:`capture` runs the window under ``jax.profiler`` (no Python function
tracing: only the device's operations and the host spans the benchmark
names ``bench.*``). :func:`read_xplane` pulls the device operations and the
host spans out of the trace file, and :func:`reduce` turns them into the
device's busy and idle time over the window, the operations that took most
time (innermost ops only: a loop's event holds its body's), the time in
cross-chip collectives, and the longest idle gaps, each named by the host
span that covered most of it.

The reduction works on plain ``(name, start_ns, end_ns)`` lists, so
``perfbench/tests`` checks it on a hand-made trace.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import shutil
import tempfile

WINDOW_SPAN = "bench.window"
_TOP = 10
# XLA's collective ops, by the head of their names (``all-gather.3``,
# ``all_gather.15``, ``all-reduce-start.1``, ``collective-permute-done``, ...)
COLLECTIVES = ("all-gather", "all-reduce", "collective-permute", "all-to-all",
               "reduce-scatter")


@dataclasses.dataclass
class TraceSummary:
    busy_s: float  # union of device operation intervals, mean over the chips
    window_s: float
    device_ops: list  # [[name, seconds], ...] most time first
    idle_gaps: list  # [[host span, seconds], ...] longest first
    # union of collective op intervals, mean over the chips; None where no
    # chip ran one
    collective_s: float | None = None

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


@contextlib.contextmanager
def capture(enabled: bool):
    """Trace the block when ``enabled``; yields a holder whose ``.path``
    names the trace file afterwards (None when not traced)."""
    holder = type("Captured", (), {"path": None, "dir": None})()
    if not enabled:
        yield holder
        return
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    holder.dir = tempfile.mkdtemp(prefix="perfbench-trace-")
    jax.profiler.start_trace(holder.dir, profiler_options=opts)
    try:
        yield holder
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(holder.dir, "**", "*.xplane.pb"), recursive=True)
    holder.path = found[0] if found else None


def discard(holder) -> None:
    if holder.dir:
        shutil.rmtree(holder.dir, ignore_errors=True)


def op_name(event_name: str) -> str:
    """``fusion.12`` of an XLA Ops event named by its HLO text
    (``%fusion.12 = f32[...] fusion(...)``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def is_collective(name: str) -> bool:
    return name.replace("_", "-").startswith(COLLECTIVES)


def read_xplane(path: str):
    """(device ops per device plane, host ``bench.*`` spans) of one trace.

    A device op is an event of a TPU plane's "XLA Ops" line. Each list
    holds ``(name, start_ns, end_ns)``. On a TPU v5e the device's clock runs
    about a millisecond behind the host's in these files: nothing here
    needs them closer than that.
    """
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices, spans = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = [
                (op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                for line in plane.lines if line.name == "XLA Ops"
                for e in line.events
            ]
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            spans.extend(
                (e.name, e.start_ns, e.start_ns + e.duration_ns)
                for line in plane.lines
                for e in line.events
                if e.name.startswith("bench.")
            )
    return devices, spans


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged ``(start, end)`` intervals, clipped to ``[lo, hi]``."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for _, s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: list[tuple[float, float]], lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle intervals of ``[lo, hi]`` between merged busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def leaves(ops):
    """The ops that contain no other op: a ``while`` op's events enclose
    those of its body on the same line, and only the body's count as time
    spent in an operation."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    parent = [False] * len(ops)
    open_ = []
    for i, (_, s, e) in enumerate(ops):
        while open_ and ops[open_[-1]][2] <= s:
            open_.pop()
        if open_:
            parent[open_[-1]] = True
        open_.append(i)
    return [o for o, p in zip(ops, parent) if not p]


def _cover_name(gap: tuple[float, float], spans) -> str:
    """The ``bench.*`` host span, other than the window, that overlaps the
    gap most; of spans that overlap it equally, the shortest (innermost)."""
    best, name = (0.0, 0.0), "unattributed"
    for n, s, e in spans:
        if n == WINDOW_SPAN:
            continue
        key = (min(e, gap[1]) - max(s, gap[0]), s - e)
        if key[0] > 0 and key > best:
            best, name = key, n
    return name


def reduce(devices, spans) -> TraceSummary:
    """Busy time, idle time, top ops, collective time and named idle gaps
    over the window, which is the ``bench.window`` host span. Times are the
    mean over the devices (chips) of the trace."""
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError("the trace holds no bench.window span")
    lo, hi = windows[0]
    if not devices or not any(devices):
        raise ValueError("the trace holds no device operation")
    busy_ns, coll_ns, totals = 0.0, 0.0, {}
    all_gaps = []
    any_coll = False
    for ops in devices:
        merged = union(ops, lo, hi)
        busy_ns += sum(e - s for s, e in merged)
        coll = [o for o in ops if is_collective(o[0])]
        any_coll = any_coll or bool(coll)
        coll_ns += sum(e - s for s, e in union(coll, lo, hi))
        all_gaps += gaps(merged, lo, hi)
        for name, s, e in leaves(ops):
            d = min(e, hi) - max(s, lo)
            if d > 0:
                totals[name] = totals.get(name, 0.0) + d
    top_ops = sorted(totals.items(), key=lambda kv: -kv[1])[:_TOP]
    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:_TOP]
    return TraceSummary(
        busy_s=busy_ns / len(devices) / 1e9,
        window_s=(hi - lo) / 1e9,
        device_ops=[[n, d / len(devices) / 1e9] for n, d in top_ops],
        idle_gaps=[[_cover_name(g, spans), (g[1] - g[0]) / 1e9] for g in longest],
        collective_s=coll_ns / len(devices) / 1e9 if any_coll else None,
    )
