"""Reduce a JAX profiler trace to device operations, programs and host spans.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes with nothing but
``jax.profiler.ProfileData``.  Two layouts are understood:

- a TPU device plane (``/device:TPU:<n>``): operations on its ``XLA Ops``
  line, program executions on its ``XLA Modules`` line;
- the CPU backend, whose operations run on host threads and carry an
  ``hlo_module`` stat; a program execution is the span of its operations
  that share a ``run_id``.

Host spans are the ``bench.*`` annotations the harness puts around its
calls.  All times are nanoseconds on the trace's one clock.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "bench."


@dataclass
class Event:
    device: int
    name: str
    start: float
    dur: float
    module: str = ""

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class Trace:
    ops: List[Event] = field(default_factory=list)
    modules: List[Event] = field(default_factory=list)
    spans: List[Event] = field(default_factory=list)

    @property
    def devices(self) -> List[int]:
        return sorted({e.device for e in self.ops})

    def window(self) -> Tuple[float, float]:
        """From the first harness span's start to the last one's end."""
        return (min(s.start for s in self.spans),
                max(s.end for s in self.spans))


def _stats(ev) -> Dict:
    try:
        return {k: v for k, v in ev.stats}
    except Exception:           # noqa: BLE001 — stats are optional
        return {}


_MODULE_SUFFIX = re.compile(r"\(\d+\)$")


def op_name(text: str) -> str:
    """A TPU op event is named by its whole HLO instruction
    (``%fusion.12 = bf16[...] fusion(...)``): keep the instruction name."""
    return text.split(" = ", 1)[0].lstrip("%")


def module_name(name: str) -> str:
    """``jit__train_step_impl(123)`` -> ``jit__train_step_impl``."""
    return _MODULE_SUFFIX.sub("", name.strip())


def from_profile(data) -> Trace:
    tr = Trace()
    cpu_runs: Dict[Tuple, List[Event]] = defaultdict(list)
    for plane in data.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    tr.ops += [Event(dev, op_name(e.name), e.start_ns,
                                     e.duration_ns) for e in line.events]
                elif line.name == "XLA Modules":
                    tr.modules += [Event(dev, module_name(e.name),
                                         e.start_ns, e.duration_ns)
                                   for e in line.events]
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    tr.spans.append(Event(-1, e.name, e.start_ns,
                                          e.duration_ns))
                    continue
                st = _stats(e)
                if "hlo_module" in st and "hlo_op" in st:
                    ev = Event(int(st.get("device_ordinal", 0)), e.name,
                               e.start_ns, e.duration_ns,
                               module=str(st["hlo_module"]))
                    tr.ops.append(ev)
                    cpu_runs[(ev.device, ev.module, st.get("run_id"))
                             ].append(ev)
    for (dev, mod, _), evs in cpu_runs.items():
        start = min(e.start for e in evs)
        tr.modules.append(Event(dev, mod, start,
                                max(e.end for e in evs) - start))
    tr.ops.sort(key=lambda e: (e.device, e.start))
    tr.modules.sort(key=lambda e: (e.device, e.start))
    tr.spans.sort(key=lambda e: e.start)
    _assign_modules(tr)
    return tr


def _assign_modules(tr: Trace) -> None:
    """Give each operation the program execution it lies in."""
    by_dev: Dict[int, List[Event]] = defaultdict(list)
    for m in tr.modules:
        by_dev[m.device].append(m)
    for op in tr.ops:
        if op.module:
            continue
        for m in by_dev.get(op.device, ()):
            if m.start <= op.start < m.end:
                op.module = m.name
                break


def load(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(files[-1]))


# ------------------------------------------------------------ reductions


def clip(events: List[Event], lo: float, hi: float) -> List[Tuple[float,
                                                                   float]]:
    out = []
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out.append((s, t))
    return out


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float,
                                                                float]]:
    merged: List[List[float]] = []
    for s, t in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t)
        else:
            merged.append([s, t])
    return [(s, t) for s, t in merged]


def busy_ns(tr: Trace, device: int, lo: float, hi: float) -> float:
    """Time in [lo, hi] in which some operation ran on ``device``."""
    ivs = union(clip([e for e in tr.ops if e.device == device], lo, hi))
    return sum(t - s for s, t in ivs)


def idle_gaps(tr: Trace, device: int, lo: float, hi: float
              ) -> List[Tuple[float, float]]:
    """Intervals of [lo, hi] in which nothing ran on ``device``."""
    gaps, cur = [], lo
    for s, t in union(clip([e for e in tr.ops if e.device == device],
                           lo, hi)):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, t)
    if hi > cur:
        gaps.append((cur, hi))
    return gaps


def span_at(tr: Trace, t: float) -> Optional[str]:
    """The innermost harness span that covers time ``t``."""
    best = None
    for s in tr.spans:
        if s.start <= t < s.end and (best is None or s.dur < best.dur):
            best = s
    return best.name if best else None


def module_runs(tr: Trace, pattern: str) -> Dict[int, List[Event]]:
    """Executions of programs whose name contains ``pattern``, per
    device."""
    out: Dict[int, List[Event]] = defaultdict(list)
    for m in tr.modules:
        if pattern in m.name:
            out[m.device].append(m)
    return out


def op_time_ns(tr: Trace, regex: str, module: Optional[str] = None
               ) -> Dict[int, float]:
    """Total device time of operations whose name matches ``regex``
    (inside programs whose name contains ``module``), per device."""
    rx = re.compile(regex)
    out: Dict[int, float] = defaultdict(float)
    for op in tr.ops:
        if rx.search(op.name) and (module is None or module in op.module):
            out[op.device] += op.dur
    return out
