"""Read a traced run by the program's own scopes and spans.

The program names the phases of its train step and sync round with
``jax.named_scope`` (``SCOPES``), which reach the ``op_name`` metadata of
its compiled programs, and puts ``repro.*`` host spans around its own calls
(``Trainer.train_step``, ``Trainer.maybe_sync`` and the sync round's
dispatch).  Both are read from the same ``.xplane.pb`` that
``chipbench/trace.py`` reduces:

- the compiled HLO of every program the process holds is in the trace's
  ``/host:metadata`` plane, one ``Hlo Proto`` per executable;
- an operation's scope is the first of ``SCOPES`` in the ``op_name`` of
  its instruction in the HLO of the executable it ran in (by program
  id), joined by instruction name (TPU: the ``XLA Ops`` event's name;
  CPU: its ``hlo_op`` stat).  ``train_forward`` under a ``transpose(`` is
  the backward pass, ``BACKWARD``.  A fusion carries its root's
  ``op_name``; an instruction the compiler added without one takes the
  nearest traced one in the dataflow (``hlo_op_names``);
- only top-level operations are counted: one lying wholly inside another
  on the same device (the body of a ``while``) is part of its parent.

Every operation keeps its whole ``op_name`` too, so that a model's own
``jax.named_scope`` nested under ``train_forward`` (say ``moe_experts``) is
read by ``named_ms`` without joining the trace again, also where it lies in
the body of the layer loop, which top-level counting folds into the loop.

A program traced without scopes, or a trace without the HLO, gives every
operation the scope "", and a trace without ``repro.*`` spans gives no
program spans: the readers then return None.
"""
from __future__ import annotations

import bisect
import functools
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, Iterator, List, Optional, Tuple

from chipbench import trace

# where ``run.py --trace 1`` writes its trace (``run.TRACE_DIR``)
TRACE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         ".trace")
PROGRAM_SPAN_PREFIX = "repro."
SCOPES = ("train_forward", "train_update", "sync_encode", "sync_ef",
          "sync_ring", "sync_apply")
BACKWARD = "train_backward"


@dataclass
class Scoped:
    """Every operation of a trace with its ``op_name`` ("" for none), in
    ``_nesting_order``, and the program's host spans."""
    devices: List[int]
    ops: List[Tuple[trace.Event, str]] = field(default_factory=list)
    program_spans: List[trace.Event] = field(default_factory=list)

    @functools.cached_property
    def top(self) -> List[Tuple[trace.Event, str]]:
        """The top-level operations with their scopes (``scope_of``)."""
        return [(op, scope_of(name)) for op, name in _outermost(self.ops)]


# ------------------------------------------------------------ the xplane

def _varint(b: memoryview, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b: memoryview) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one serialized protobuf message; a
    length-delimited value is a memoryview of its bytes."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        elif wire == 2:
            length, i = _varint(b, i)
            v, i = b[i:i + length], i + length
        else:
            raise ValueError(f"protobuf wire type {wire} not understood")
        yield key >> 3, v


def hlo_programs(xspace: bytes) -> Dict[int, Tuple[str, str]]:
    """Program id -> (module name, HLO text) of each executable in the
    ``Hlo Proto`` stats of a serialized XSpace's ``/host:metadata`` plane
    (XPlane: name 2, event_metadata 4, stat_metadata 5; XEventMetadata: id
    1, name 2, stats 5; XStat: metadata_id 1, bytes_value 6; HloProto:
    hlo_module 1)."""
    from jax._src.lib import _jax

    opts = _jax.HloPrintOptions()
    out: Dict[int, Tuple[str, str]] = {}
    for num, plane in _fields(memoryview(xspace)):
        if num != 1:
            continue
        fs = list(_fields(plane))
        if [bytes(v) for k, v in fs if k == 2] != [b"/host:metadata"]:
            continue
        stat_names = {}
        for k, entry in fs:
            if k == 5:
                meta = dict(_fields(dict(_fields(entry)).get(2, b"")))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b""))
        for k, entry in fs:
            if k != 4:
                continue
            meta = list(_fields(dict(_fields(entry)).get(2, b"")))
            pid = dict(meta).get(1, 0)
            name = bytes(dict(meta).get(2, b"")).decode()
            for kk, stat in meta:
                st = dict(_fields(stat)) if kk == 5 else {}
                if stat_names.get(st.get(1)) != b"Hlo Proto" or 6 not in st:
                    continue
                module = bytes(dict(_fields(st[6])).get(1, b""))
                out[pid] = (trace.module_name(name),
                            _jax.HloModule.from_serialized_hlo_module_proto(
                                module).to_string(opts))
    return out


_PROGRAM_ID = re.compile(r"\((\d+)\)$")


def executions(data) -> List[Tuple[int, float, float, str, int]]:
    """(device, start, end, module name, program id) of each program
    execution in a ``jax.profiler.ProfileData``: TPU, the events of a
    device plane's ``XLA Modules`` line, named ``<module>(<program id>)``;
    CPU, the span of the operations that share a ``run_id``."""
    out = []
    cpu: Dict[tuple, Tuple[float, float]] = {}
    for plane in data.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if m:
            for line in plane.lines:
                if line.name != "XLA Modules":
                    continue
                for e in line.events:
                    pid = _PROGRAM_ID.search(e.name.strip())
                    if pid:
                        out.append((int(m.group(1)), e.start_ns,
                                    e.start_ns + e.duration_ns,
                                    trace.module_name(e.name),
                                    int(pid.group(1))))
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                st = trace._stats(e)
                if "hlo_op" not in st or "program_id" not in st:
                    continue
                key = (int(st.get("device_ordinal", 0)),
                       str(st["hlo_module"]), int(st["program_id"]),
                       st.get("run_id"))
                lo, hi = cpu.get(key, (e.start_ns,
                                       e.start_ns + e.duration_ns))
                cpu[key] = (min(lo, e.start_ns),
                            max(hi, e.start_ns + e.duration_ns))
    out += [(dev, lo, hi, mod, pid)
            for (dev, mod, pid, _), (lo, hi) in cpu.items()]
    return sorted(out)


def program_spans(data) -> List[trace.Event]:
    """The ``repro.*`` host spans of a ``jax.profiler.ProfileData``."""
    out = [trace.Event(-1, e.name, e.start_ns, e.duration_ns)
           for plane in data.planes if plane.name.startswith("/host:")
           for line in plane.lines for e in line.events
           if e.name.startswith(PROGRAM_SPAN_PREFIX)]
    return sorted(out, key=lambda e: e.start)


# ------------------------------------------------------------ HLO

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s*\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%?([^\s=]+) = ")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"calls=%?([^\s,}]+)")
_REF = re.compile(r"%([^\s,(){}]+)")
_SCOPE = re.compile(r"(?<![\w.])(" + "|".join(SCOPES) + r")(?!\w)")


def hlo_op_names(text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` metadata ("" for none) of one
    compiled HLO module's text.

    A fusion without metadata of its own takes its fused computation's
    root's or, where the root has none (a convert the compiler added),
    that of the last instruction before it that has one.  Any other
    instruction the compiler added without a traced ``op_name`` (a
    relayout copy, which carries at most its argument's path, a loop of
    its own, a broadcast) takes the nearest traced one in the dataflow:
    first among its users and theirs, then among its operands and
    theirs."""
    names: Dict[str, str] = {}
    calls: Dict[str, str] = {}
    refs: Dict[str, List[str]] = {}
    where: Dict[str, str] = {}          # instruction -> its computation
    last: Dict[str, str] = {}           # computation -> its root's op_name
    comp = ""
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m and " = " not in line:
            comp = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(2)
        meta = _OP_NAME.search(line)
        names[name] = meta.group(1) if meta else ""
        if meta:
            last[comp] = meta.group(1)
        c = _CALLS.search(line)
        if c:
            calls[name] = c.group(1)
        refs[name] = _REF.findall(line.split(" = ", 1)[1])
        where[name] = comp
    for name, comp in calls.items():
        if not names[name] and comp in last:
            names[name] = last[comp]
    fused = set(calls.values())
    operands = {n: [r for r in rs if r in names] for n, rs in refs.items()}
    users: Dict[str, List[str]] = defaultdict(list)
    for n, rs in operands.items():
        for r in rs:
            users[r].append(n)
    own = {n: o if o.startswith("jit(") else "" for n, o in names.items()}
    for name, comp in where.items():
        if not own[name] and comp not in fused:
            names[name] = _nearest(name, own, (users, operands)) or \
                names[name]
    return names


def _nearest(name: str, own: Dict[str, str], graphs) -> str:
    """The ``op_name`` of the instruction nearest to ``name`` that has
    one, breadth first through each graph in turn."""
    for edges in graphs:
        seen, frontier = {name}, [name]
        while frontier:
            step = []
            for x in frontier:
                for y in edges.get(x, ()):
                    if y in seen:
                        continue
                    if own[y]:
                        return own[y]
                    seen.add(y)
                    step.append(y)
            frontier = step
    return ""


def scope_of(op_name: str) -> str:
    """The scope an ``op_name`` belongs to: the first of ``SCOPES`` in it,
    ``train_forward`` under a ``transpose(`` being ``BACKWARD``; "" for
    none."""
    m = _SCOPE.search(op_name)
    if m is None:
        return ""
    if m.group(1) == "train_forward" and "transpose(" in op_name[:m.start()]:
        return BACKWARD
    return m.group(1)


# ------------------------------------------------------------ the join

def op_names(tr: trace.Trace, runs, programs: Dict[int, Tuple[str, str]]
             ) -> Dict[int, str]:
    """``id`` of each operation of an execution in ``runs``
    (``executions``) -> the ``op_name`` of its instruction in the HLO of
    that execution's program (``hlo_programs``, ``hlo_op_names``)."""
    by_dev: Dict[int, List[trace.Event]] = defaultdict(list)
    for op in tr.ops:
        by_dev[op.device].append(op)
    starts = {d: [op.start for op in ops] for d, ops in by_dev.items()}
    names: Dict[int, Dict[str, str]] = {}
    out: Dict[int, str] = {}
    for dev, lo, hi, module, pid in runs:
        if pid not in programs or dev not in by_dev:
            continue
        if pid not in names:
            names[pid] = hlo_op_names(programs[pid][1])
        a = bisect.bisect_left(starts[dev], lo)
        b = bisect.bisect_left(starts[dev], hi)
        for op in by_dev[dev][a:b]:
            if op.module == module:
                out[id(op)] = names[pid].get(op.name, "")
    return out


def _outermost(ops: Iterable[Tuple[trace.Event, str]]
               ) -> Iterator[Tuple[trace.Event, str]]:
    """Of ``(op, op_name)`` pairs sorted by ``_nesting_order``, those whose
    op does not lie wholly inside another of theirs on the same device."""
    end: Dict[int, float] = {}
    for op, name in ops:
        if op.end > end.get(op.device, float("-inf")):
            end[op.device] = op.end
            yield op, name


def _nesting_order(pair: Tuple[trace.Event, str]):
    op = pair[0]
    return (op.device, op.start, -op.dur)


def top_level(tr: trace.Trace) -> List[trace.Event]:
    """Operations not lying wholly inside another on the same device."""
    return [op for op, _ in _outermost(sorted(((op, "") for op in tr.ops),
                                              key=_nesting_order))]


def from_xspace(tr: trace.Trace, xspace: bytes) -> Scoped:
    """``tr``, the reduction of the serialized XSpace ``xspace``, read by
    scope and program span."""
    from jax.profiler import ProfileData

    data = ProfileData.from_serialized_xspace(xspace)
    names = op_names(tr, executions(data), hlo_programs(xspace))
    return Scoped(
        devices=tr.devices,
        ops=sorted(((op, names.get(id(op), "")) for op in tr.ops),
                   key=_nesting_order),
        program_spans=program_spans(data))


def load(tr: trace.Trace, trace_dir: str) -> Scoped:
    """``from_xspace`` on the file ``trace.load`` read ``tr`` from."""
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    with open(files[-1], "rb") as f:
        return from_xspace(tr, f.read())


_last: List[Tuple[trace.Trace, Scoped]] = []


def of(ctx) -> Scoped:
    """The run's trace read by scope, once for all its readers."""
    if not _last or _last[0][0] is not ctx.trace:
        _last[:] = [(ctx.trace, load(ctx.trace, TRACE_DIR))]
    return _last[0][1]


# ------------------------------------------------------------ reductions

def split_ns(sc: Scoped, module: str) -> Dict[str, float]:
    """Device time of the top-level operations of the programs whose name
    contains ``module``, by scope ("" for none), summed over devices."""
    out: Dict[str, float] = defaultdict(float)
    for op, scope in sc.top:
        if module in op.module:
            out[scope] += op.dur
    return dict(out)


def scope_ms(sc: Scoped, scope: str, count: int) -> Optional[float]:
    """Device time of the top-level operations of one scope per device
    and per ``count`` (the traced steps or rounds), in ms; None where no
    operation has the scope."""
    durs = [op.dur for op, s in sc.top if s == scope]
    if not durs:
        return None
    return sum(durs) / len(sc.devices) / count / 1e6


def named_ms(sc: Scoped, name: str, count: int,
             backward: bool) -> Optional[float]:
    """Device time of the operations whose ``op_name`` holds ``name`` as a
    whole scope component, per device and per ``count``, in ms; None where
    none does.  A component lies between two ``/`` and counts with the
    transformations wrapped around it: ``train_forward`` is one of
    ``vmap(jvp(train_forward))``.  ``backward`` True keeps the operations
    under a ``transpose(`` (the backward pass, remat in it), False the
    others.  Of these, an operation lying wholly inside another
    on its device is counted with it, as ``top_level`` counts; one in the
    body of a loop that does not hold the scope (the layer loop) is
    counted itself.  Unlike ``scope_ms`` this reads any scope at any
    depth: ``named_ms(sc, "moe_experts", steps, False)`` is the forward
    time of a model's own ``moe_experts`` scope, wherever it lies under
    ``train_forward``."""
    pattern = re.compile(r"(?:^|/)(?:[\w.-]*\()*" + re.escape(name) +
                         r"\)*(?=/|$)")

    def holds(op_name: str) -> bool:
        m = pattern.search(op_name)
        return m is not None and \
            ("transpose(" in op_name[:m.end()]) == backward

    durs = [op.dur for op, _ in _outermost(pair for pair in sc.ops
                                           if holds(pair[1]))]
    if not durs:
        return None
    return sum(durs) / len(sc.devices) / count / 1e6


def idle_in_program_spans_ns(tr: trace.Trace, sc: Scoped, device: int,
                             lo: float, hi: float) -> float:
    """Time in [lo, hi] inside some program span in which no operation ran
    on ``device``."""
    return sum((t - s) - trace.busy_ns(tr, device, s, t)
               for s, t in trace.union(trace.clip(sc.program_spans,
                                                  lo, hi)))
