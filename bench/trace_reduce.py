"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics read.

A TPU trace holds one plane per chip (``/device:TPU:<n>``) with an
``XLA Modules`` line (one event per program run, named ``jit_<fn>(<id>)``)
and an ``XLA Ops`` line (one event per operation; a loop's body ops nest
inside the loop's event), and a host plane (``/host:CPU``) with one line per
thread, on which ``jax.profiler.TraceAnnotation`` spans appear by name.

The reduction keeps, per chip:

* ``busy_s``: the union of the top-level op intervals inside the window;
* ``modules``: program runs as ``(name, start_s, end_s)``, the id dropped;
* ``ops``: top-level ops as ``(name, start_s, end_s)``, ``name`` the HLO
  instruction with its long form (operands, custom-call target) kept in
  ``long``;

and from the host: the window (the span named ``WINDOW_SPAN``), every host
span, so an idle gap on a chip can be labelled by the innermost host span
open at its middle, and the engine's dispatches (``DISPATCH_SPAN``) that lie
wholly inside the window.  Times are seconds from the window's start.

A dispatch's span runs from the engine's call into the index to the end of
the readback of its answers, so it holds all the device work of that
dispatch.  The per-dispatch readers divide by the complete dispatches and
count only the device work inside them (:func:`per_dispatch_ms`).
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.trace_window"
# the engine's span around one batch: index search and readback
DISPATCH_SPAN = "repro.engine.dispatch"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_MODULE_ID = re.compile(r"\(\d+\)$")
# host events that only say a thread is parked, never what it waits on
_HOST_NOISE = ("ThreadpoolListener", "MemoryAllocation", "MemoryDeallocation")
# spans of the Python layer (annotations, jitted calls, readbacks) say what
# the program was doing; runtime-thread events only how
_HOST_PREFER = ("repro.", "bench.", "PjitFunction(", "np.asarray",
                "DevicePut")


@dataclasses.dataclass
class Op:
    name: str            # HLO instruction name, e.g. "fusion.22"
    long: str            # the full instruction text
    start: float
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Chip:
    index: int
    modules: List[Tuple[str, float, float]]
    ops: List[Op]                      # top-level ops only
    busy_s: float
    gaps: List[Tuple[float, float]]    # idle intervals inside the window

    def in_modules(self, prefix: str, spans=None
                   ) -> List[Tuple[str, float, float]]:
        """Program runs whose name starts with ``prefix`` and that lie
        wholly inside the window, or with ``spans`` (sorted ``(start,
        end)``) wholly inside one of them."""
        runs = [m for m in self.modules if m[0].startswith(prefix)]
        if spans is None:
            return runs
        starts = [s for s, _ in spans]
        out = []
        for m in runs:
            i = bisect.bisect_right(starts, m[1]) - 1
            if i >= 0 and m[2] <= spans[i][1] + 1e-9:
                out.append(m)
        return out

    def ops_within(self, runs) -> List[Op]:
        """The top-level ops that lie inside one of the given program runs
        (runs that overlap are merged first, so no op counts twice)."""
        _, merged = _union(sorted((s, e) for _, s, e in runs))
        starts = [s for s, _ in merged]
        out = []
        for op in self.ops:
            i = bisect.bisect_right(starts, op.start) - 1
            if i >= 0 and op.end <= merged[i][1] + 1e-9:
                out.append(op)
        return out


@dataclasses.dataclass
class Reduction:
    window_s: float
    chips: List[Chip]
    host_spans: List[Tuple[str, float, float]]   # sorted by start
    dispatches: List[Tuple[float, float]]        # complete, sorted

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the chips."""
        return sum(c.busy_s for c in self.chips) / len(self.chips)

    def label(self, t: float) -> str:
        """The innermost host span open at ``t``, a span of the Python layer
        first ("no host span" if none is open)."""
        best = {True: None, False: None}
        for name, s, e in self.host_spans:
            if s > t:
                break
            pref = name.startswith(_HOST_PREFER)
            b = best[pref]
            if e >= t and (b is None or e - s < b[2] - b[1]):
                best[pref] = (name, s, e)
        pick = best[True] or best[False]
        return pick[0] if pick else "no host span"

    def device_ops(self, top: int = 10) -> List[List]:
        """[[op, seconds]] summed over chips, the ``top`` largest; loop and
        call ops count as one op (their bodies nest inside them)."""
        tot: Dict[str, float] = {}
        for c in self.chips:
            for op in c.ops:
                key = _short(op.long)
                tot[key] = tot.get(key, 0.0) + op.dur
        return [[k, v] for k, v in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:top]]

    def idle_gaps(self, top: int = 10) -> List[List]:
        """[[label, seconds]]: the ``top`` longest idle gaps over all chips,
        each labelled by the host span open at its middle."""
        gaps = [(e - s, c.index, s, e) for c in self.chips
                for s, e in c.gaps]
        gaps.sort(reverse=True)
        return [[f"chip{i}: {self.label((s + e) / 2)}", d]
                for d, i, s, e in gaps[:top]]


def _short(long: str) -> str:
    """``%fusion.22 = s8[...] fusion(...)`` -> ``fusion.22``; a custom
    call keeps its target: ``fused_phase1_pallas.1 (tpu_custom_call)``."""
    name = long.split(" = ")[0].lstrip("%")
    m = re.search(r'custom_call_target="([^"]+)"', long)
    return f"{name} ({m.group(1)})" if m else name


def _union(intervals) -> Tuple[float, List[Tuple[float, float]]]:
    """-> (covered length, merged intervals) of sorted (start, end)."""
    merged: List[List[float]] = []
    for s, e in intervals:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), [tuple(m) for m in merged]


def _top_level(events):
    """Drop events nested inside an earlier event of the same line."""
    out, end = [], float("-inf")
    for ev in sorted(events, key=lambda x: (x[1], -x[2])):
        if ev[1] >= end:
            out.append(ev)
            end = ev[2]
        elif ev[2] > end:           # overlaps without nesting: keep it
            out.append(ev)
            end = ev[2]
    return out


def reduce_events(device: Dict[int, Dict[str, list]],
                  host: List[Tuple[str, float, float]]) -> Reduction:
    """Reduce raw events (seconds, any origin) to a :class:`Reduction`.

    ``device[chip]`` maps a line name (``"XLA Modules"``, ``"XLA Ops"``) to
    ``(name, start, end)`` events; ``host`` lists host spans.  The window is
    the host span named ``WINDOW_SPAN``; without one it is the extent of the
    device events."""
    win = [h for h in host if h[0] == WINDOW_SPAN]
    if win:
        w0, w1 = win[0][1], win[0][2]
    else:
        ends = [e for lines in device.values() for evs in lines.values()
                for _, _, e in evs]
        starts = [s for lines in device.values() for evs in lines.values()
                  for _, s, _ in evs]
        if not starts:
            raise ValueError("the trace holds no device events")
        w0, w1 = min(starts), max(ends)
    chips = []
    for idx in sorted(device):
        lines = device[idx]
        mods = sorted((_MODULE_ID.sub("", n), s - w0, e - w0)
                      for n, s, e in lines.get("XLA Modules", ())
                      if s >= w0 and e <= w1)
        raw = [(n, max(s, w0) - w0, min(e, w1) - w0)
               for n, s, e in lines.get("XLA Ops", ()) if e > w0 and s < w1]
        top = _top_level(raw)
        busy, merged = _union((s, e) for _, s, e in top)
        gaps, t = [], 0.0
        for s, e in merged:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < w1 - w0:
            gaps.append((t, w1 - w0))
        ops = [Op(n.split(" = ")[0].lstrip("%"), n, s, e) for n, s, e in top]
        chips.append(Chip(idx, mods, ops, busy, gaps))
    spans = sorted(((n, s - w0, e - w0) for n, s, e in host
                    if n != WINDOW_SPAN and e > w0 and s < w1
                    and not n.startswith(_HOST_NOISE)),
                   key=lambda h: h[1])
    dispatches = sorted({(s, e) for n, s, e in spans
                         if n == DISPATCH_SPAN and s >= 0
                         and e <= w1 - w0})
    return Reduction(w1 - w0, chips, spans, dispatches)


def load(path: str) -> Reduction:
    """Reduce the ``.xplane.pb`` at ``path`` (or the newest one under a
    profile directory; ``.gz`` is read too)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    device: Dict[int, Dict[str, list]] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        if m:
            lines = device.setdefault(int(m.group(1)), {})
            for line in plane.lines:
                if line.name in ("XLA Modules", "XLA Ops"):
                    lines[line.name] = [
                        (e.name, e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend((e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                            for e in line.events if e.duration_ns > 0)
    return reduce_events(device, host)


def is_kernel(op: Op) -> bool:
    """A Pallas kernel: a custom call with the ``tpu_custom_call`` target."""
    return 'custom_call_target="tpu_custom_call"' in op.long


def per_dispatch_ms(trace: Reduction, chip: Chip, programs,
                    op_filter=None) -> Optional[float]:
    """Device ms per complete dispatch on ``chip`` of the runs of the
    programs whose names start with one of ``programs``: the runs' own
    lengths, or with ``op_filter`` the top-level ops inside them that it
    keeps.  Only runs inside a complete dispatch count, and the count of
    complete dispatches divides; None when the window holds none."""
    if not trace.dispatches:
        return None
    total = 0.0
    for prefix in programs:
        runs = chip.in_modules(prefix, trace.dispatches)
        if op_filter is None:
            total += sum(e - s for _, s, e in runs)
        else:
            total += sum(op.dur for op in chip.ops_within(runs)
                         if op_filter(op))
    return total / len(trace.dispatches) * 1e3


def phase1_kernel_ms(trace: Reduction) -> Optional[float]:
    """Device ms per complete dispatch of the Pallas kernels inside the
    query-phase program, mean over the chips; None when no chip ran one."""
    per_chip = [per_dispatch_ms(trace, c, ("jit__query_phase",), is_kernel)
                for c in trace.chips]
    per_chip = [v for v in per_chip if v]
    return sum(per_chip) / len(per_chip) if per_chip else None
