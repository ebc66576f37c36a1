"""Device time by SAAT phase, and the program's own spans on the device's clock.

This extends :mod:`perfbench.trace`, whose reduction the accepted metrics
read and which this module leaves as it is:

* :func:`read_xplane` returns, for each chip, every device operation under
  the HLO head that names it (instruction, result shape, opcode) and the
  module execution ("XLA Modules" event, ``jit_saat_search(<id>)``) that
  ran it, and the host spans of the benchmark (``bench.*``) and of the
  program (``serve.*``), each with its tags.
* :func:`hlo_scopes` maps each instruction of a compiled module's text
  (``jax.stages.Compiled.as_text()``) to the ``saat.*`` scope that its
  metadata carries: its own, a fusion's root's, or else the one that most
  of its fused instructions carry. Instructions the compiler made from
  nothing carry none and count as ``unscoped``. A TPU trace's operations
  carry no metadata, so :func:`module_scopes` joins each executed module to
  the compiled text that holds the heads it ran.
* :func:`reduce` turns them into the device seconds of each phase in the
  ``bench.window`` span, the flushes (``serve.flush`` spans) in it, the
  offset of the device's clock from the host's, and the longest idle gaps,
  each named by the innermost ``bench.*`` or ``serve.*`` span over it.

Like :mod:`perfbench.trace` it works on plain lists, so ``perfbench/tests``
checks it on a hand-made trace. Run it on a trace file, from the root of a
checkout, with

    python3 -m perfbench.phases <trace.xplane.pb> <compiled module text> ...

which prints the reduction as one JSON object.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re

from perfbench.trace import WINDOW_SPAN, gaps, leaves, op_name, union

PHASES = ("saat.plan", "saat.slots", "saat.gather", "saat.select")
UNSCOPED = "unscoped"
SPAN_PREFIXES = ("bench.", "serve.")
DISPATCH_SPAN = "serve.dispatch"
FLUSH_SPAN = "serve.flush"
_TOP = 10


@dataclasses.dataclass
class PhaseSummary:
    phase_s: dict  # device seconds per phase in the window, UNSCOPED included
    busy_s: float  # union of device operation intervals, mean over the chips
    flushes: int  # serve.flush spans that start in the window
    clock_offset_ms: float | None  # least (execution start - its dispatch)
    idle_gaps: list  # [[innermost host span, seconds], ...] longest first

    def per_flush_ms(self, phase: str) -> float | None:
        if not self.flushes:
            return None
        return 1e3 * self.phase_s.get(phase, 0.0) / self.flushes


def scope_of(name_stack: str) -> str | None:
    """The innermost ``saat.*`` component of an op's name stack
    (``jit(saat_search)/saat.gather/jit(take_along_axis)/gather``)."""
    found = [p for p in name_stack.split("/") if p in PHASES]
    return found[-1] if found else None


_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) ")
_INSTRUCTION = re.compile(r"^\s*(ROOT )?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")
# instruction, result shape, opcode: of an HLO text line, or of a TPU op
# event, which is named by its instruction's text without the metadata
_HEAD = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\(")


def head(text: str) -> tuple[str, str, str] | None:
    m = _HEAD.match(text)
    return m.groups() if m else None


def hlo_scopes(text: str) -> dict[str, str]:
    """Instruction name -> ``saat.*`` scope for every instruction of a
    compiled module that has one. A fusion without a scope of its own takes
    its fused computation's root's, or else the one most of the fused
    instructions carry."""
    own, roots, members, fusions = {}, {}, collections.defaultdict(list), {}
    comp = None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m and line.rstrip().endswith("{"):
            comp = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if m is None or comp is None:
            continue
        name = m.group(2)
        op = _OP_NAME.search(line)
        scope = scope_of(op.group(1)) if op else None
        if scope:
            own[name] = scope
            members[comp].append(scope)
            if m.group(1):
                roots[comp] = scope
        calls = _CALLS.search(line)
        if calls and " fusion(" in line:
            fusions[name] = calls.group(1)
    out = dict(own)
    for name, comp in fusions.items():
        if name in out:
            continue
        if comp in roots:
            out[name] = roots[comp]
        elif members[comp]:
            out[name] = collections.Counter(members[comp]).most_common(1)[0][0]
    return out


def read_xplane(path: str):
    """(devices, host spans) of one trace.

    ``devices`` holds one ``(ops, runs)`` per TPU plane: ``runs`` the module
    executions ``(module, start_ns, end_ns)`` of its "XLA Modules" line,
    ``ops`` the operations ``(key, start_ns, end_ns)`` of its "XLA Ops"
    line, keyed ``(module, instruction, result shape, opcode)`` by the
    execution that encloses them. A host span is ``(name, start_ns,
    end_ns, tags)`` of a ``bench.*`` or ``serve.*`` annotation.
    """
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices, spans = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            events = {line.name: list(line.events) for line in plane.lines}
            runs = sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in events.get("XLA Modules", [])), key=lambda r: r[1])
            starts = [s for _, s, _ in runs]
            ops = []
            for e in events.get("XLA Ops", []):
                i = bisect.bisect_right(starts, e.start_ns) - 1
                module = runs[i][0] if i >= 0 and e.start_ns < runs[i][2] else None
                key = (module,) + (head(e.name) or (op_name(e.name), "", ""))
                ops.append((key, e.start_ns, e.start_ns + e.duration_ns))
            devices.append((ops, runs))
        elif plane.name.startswith("/host:"):
            spans.extend(
                (e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                for line in plane.lines
                for e in line.events
                if e.name.startswith(SPAN_PREFIXES)
            )
    return devices, spans


def module_scopes(devices, texts) -> dict[str, dict[str, str]]:
    """Executed module -> :func:`hlo_scopes` of the compiled text that holds
    the most of the heads it ran: all of them, for the text it was compiled
    from. The executables of one engine share a module name and many
    instruction names, not their shapes."""
    texts = list(texts)
    heads = [{h for line in t.splitlines() if (h := head(line))} for t in texts]
    ran = collections.defaultdict(set)
    for ops, _ in devices:
        for key, _, _ in ops:
            ran[key[0]].add(key[1:])
    out = {}
    for module, seen in ran.items():
        best = max(range(len(texts)), key=lambda i: len(seen & heads[i]), default=None)
        if best is not None and seen & heads[best]:
            out[module] = hlo_scopes(texts[best])
    return out


def clock_offset_ns(runs, spans) -> float | None:
    """The least, over the flushes, of (start of the flush's module
    execution on the device) - (start of its ``serve.dispatch`` span, the
    one nearest in time). Negative where the device's clock runs behind the
    host's. None without dispatch spans or executions."""
    dispatch = [s for n, s, _, _ in spans if n == DISPATCH_SPAN]
    if not dispatch or not runs:
        return None
    return min(s - min(dispatch, key=lambda t: abs(s - t)) for _, s, _ in runs)


def _innermost(gap, spans) -> str:
    """The span that is innermost over most of the gap: at each instant the
    covering span that started last (a child starts after its parent), the
    window excluded."""
    lo, hi = gap
    inside = sorted((s, -e, n) for n, s, e, _ in spans
                    if n != WINDOW_SPAN and s < hi and e > lo)
    cuts = sorted({lo, hi} | {t for s, ne, _ in inside for t in (s, -ne) if lo < t < hi})
    self_time = collections.Counter()
    for a, b in zip(cuts, cuts[1:]):
        cover = [n for s, ne, n in inside if s <= a and -ne >= b]
        if cover:
            self_time[cover[-1]] += b - a
    return self_time.most_common(1)[0][0] if self_time else "unattributed"


def reduce(devices, spans, scopes: dict[str, dict[str, str]]) -> PhaseSummary:
    """Phase seconds, flushes, clock offset and named idle gaps over the
    ``bench.window`` span. ``scopes``: :func:`module_scopes`."""
    windows = [(s, e) for n, s, e, _ in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError("the trace holds no bench.window span")
    lo, hi = windows[0]
    if not devices or not any(ops for ops, _ in devices):
        raise ValueError("the trace holds no device operation")
    offsets = [o for o in (clock_offset_ns(runs, spans) for _, runs in devices) if o is not None]
    offset = min(offsets) if offsets else None
    shift = -offset if offset is not None and offset < 0 else 0.0
    phase_ns = collections.Counter({p: 0.0 for p in PHASES + (UNSCOPED,)})
    busy_ns, all_gaps = 0.0, []
    for ops, _ in devices:
        ops = [(k, s + shift, e + shift) for k, s, e in ops]
        merged = union(ops, lo, hi)
        busy_ns += sum(e - s for s, e in merged)
        all_gaps += gaps(merged, lo, hi)
        for (module, instruction, *_), s, e in leaves(ops):
            d = min(e, hi) - max(s, lo)
            if d > 0:
                phase_ns[scopes.get(module, {}).get(instruction, UNSCOPED)] += d
    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:_TOP]
    n = len(devices)
    return PhaseSummary(
        phase_s={p: v / n / 1e9 for p, v in phase_ns.items()},
        busy_s=busy_ns / n / 1e9,
        flushes=sum(1 for name, s, _, _ in spans if name == FLUSH_SPAN and lo <= s < hi),
        clock_offset_ms=None if offset is None else offset / 1e6,
        idle_gaps=[[_innermost(g, spans), (g[1] - g[0]) / 1e9] for g in longest],
    )


if __name__ == "__main__":
    import json
    import sys
    from pathlib import Path

    trace_path, *hlo_paths = sys.argv[1:]
    devices, spans = read_xplane(trace_path)
    summary = reduce(devices, spans,
                     module_scopes(devices, (Path(p).read_text() for p in hlo_paths)))
    print(json.dumps(dataclasses.asdict(summary)))
