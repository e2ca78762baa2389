"""Reduction of a profiler trace by the program's own spans and scopes.

The program writes ``parrot.<phase>`` host spans into the JAX profiler's
trace (``repro.core.telemetry.span``) and ``jax.named_scope`` names into its
traced code (``attn``, ``mlp``, ``head_loss``, ...), so device time can be
attributed to the layer that launched it and the scope it ran in, whatever
XLA names the programs:

  launches(pd)             host launches (``PjitFunction(<f>)``) with the
                           innermost ``parrot.*`` span that holds each
  executions(pd)           device program executions
  link(launches, execs)    each execution with its launch: by ``run_id``
                           where both sides carry it, else in launch order
                           per device; names must agree, or it raises
  span_device_seconds      device seconds per launching span
  scope_device_seconds     device seconds per scope, from op events
  innermost_label          the innermost program or benchmark span at t
  host_self_seconds        a span's time less the part its children cover
  layers(...)              the per-layer numbers of a traced run

Run as a script it builds a cell's server as the benchmark does
(``harness.build``), runs its warm-up rounds, records a trace of its
``trace_rounds`` rounds and prints these numbers:

  python3 perfbench/spans.py --workload <name> --seed <n> [--out <file>]
"""
from __future__ import annotations

import bisect
import os
import re
import sys
import warnings
from dataclasses import dataclass, field
from typing import (Dict, Iterable, List, NamedTuple, Optional, Sequence,
                    Tuple)

if __name__ == "__main__":
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from perfbench import trace  # noqa: E402
from repro.core.telemetry import SPAN_PREFIX as PREFIX  # noqa: E402
from repro.core.telemetry import WALL_SPANS  # noqa: E402

#: spans whose launches count to their own layer (the rest of the round is
#: the engine's host work)
LAUNCHING = tuple(PREFIX + n for n, own in WALL_SPANS.items() if own)
#: the client step's scopes that split its device time
STEP_SCOPES = ("attn", "mlp", "moe", "ssm", "head_loss", "embed", "opt")
_LAUNCH = re.compile(r"^PjitFunction\((.*)\)$")
_WORD = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")
_HLO_TEXT = re.compile(r"^%?([^\s=]+) = ")     # a TPU op event's name


@dataclass
class HostEvent:
    name: str
    start: float                      # ns
    end: float
    line: str                         # "<plane>/<line>": one host thread
    stats: Dict[str, object] = field(default_factory=dict)


@dataclass
class Launch:
    function: str                     # f of PjitFunction(f)
    start: float
    end: float
    line: str
    run_ids: Tuple[int, ...]
    span: Optional[HostEvent]         # innermost parrot.* span holding it


@dataclass
class Execution:
    program: str                      # jit_<f>, the run id suffix dropped
    device: str
    start: float
    end: float
    run_id: Optional[int] = None


def _stats(e) -> Dict[str, object]:
    with warnings.catch_warnings():    # jaxlib's stats type, not ours
        warnings.simplefilter("ignore", DeprecationWarning)
        return dict(e.stats)


def host_events(pd, prefix: str = "") -> List[HostEvent]:
    """Events of the host planes whose names start with ``prefix``."""
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:") and \
                plane.name != "/host:metadata":
            for ln in plane.lines:
                key = f"{plane.name}/{ln.name}"
                out += [HostEvent(e.name, e.start_ns,
                                  e.start_ns + e.duration_ns, key, _stats(e))
                        for e in ln.events if e.name.startswith(prefix)]
    return sorted(out, key=lambda e: (e.start, -e.end))


def _innermost(spans: Sequence[HostEvent], t: float,
               line: Optional[str] = None) -> Optional[HostEvent]:
    best = None
    for s in spans:
        if s.start > t:
            break
        if t <= s.end and (line is None or s.line == line) and (
                best is None or s.end - s.start < best.end - best.start):
            best = s
    return best


def innermost_label(spans: Sequence[HostEvent], t: float,
                    prefixes: Tuple[str, ...] = (PREFIX, trace.PREFIX)
                    ) -> str:
    """The innermost span with one of ``prefixes`` that holds instant ``t``
    (``spans`` sorted by start): a program phase inside the benchmark's
    round span names it."""
    s = _innermost([x for x in spans if x.name.startswith(prefixes)], t)
    return s.name if s is not None else "no host span"


def launches(pd, events: Optional[List[HostEvent]] = None) -> List[Launch]:
    """Every host launch of a program, outermost ``PjitFunction`` event per
    call, with the run ids of the executions it started (host events
    nested in it that carry ``run_id``) and its innermost program span on
    the same thread."""
    events = host_events(pd) if events is None else events
    spans = [e for e in events if e.name.startswith(PREFIX)]
    by_line: Dict[str, List[HostEvent]] = {}
    for e in events:
        by_line.setdefault(e.line, []).append(e)
    out = []
    for line, evs in by_line.items():
        starts = [e.start for e in evs]
        outer_end = -1.0
        for e in evs:
            m = _LAUNCH.match(e.name)
            if not m or e.end <= outer_end:
                continue                  # not a launch, or a nested one
            outer_end = e.end
            lo = bisect.bisect_left(starts, e.start)
            hi = bisect.bisect_right(starts, e.end)
            ids = tuple(int(x.stats["run_id"]) for x in evs[lo:hi]
                        if "run_id" in x.stats and x.end <= e.end)
            out.append(Launch(m.group(1), e.start, e.end, line, ids,
                              _innermost(spans, e.start, line)))
    return sorted(out, key=lambda x: x.start)


def executions(pd) -> List[Execution]:
    """Device program executions: the ``XLA Modules`` line of each TPU
    plane; on the CPU, the op events of one ``run_id``."""
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for ln in plane.lines:
                if ln.name == "XLA Modules":
                    for e in ln.events:
                        rid = _stats(e).get("run_id")
                        out.append(Execution(
                            trace.program_name(e.name), plane.name,
                            e.start_ns, e.start_ns + e.duration_ns,
                            None if rid is None else int(rid)))
    if out:
        return sorted(out, key=lambda x: x.start)
    runs: Dict[int, Execution] = {}
    for plane in pd.planes:
        if plane.name == "/host:CPU":
            for ln in plane.lines:
                for e in ln.events:
                    st = _stats(e)
                    if "hlo_module" not in st or "run_id" not in st:
                        continue
                    rid, end = int(st["run_id"]), e.start_ns + e.duration_ns
                    x = runs.get(rid)
                    if x is None:
                        runs[rid] = Execution(str(st["hlo_module"]),
                                              plane.name, e.start_ns, end,
                                              rid)
                    else:
                        x.start, x.end = min(x.start, e.start_ns), max(
                            x.end, end)
    return sorted(runs.values(), key=lambda x: x.start)


def _alnum(s: str) -> str:
    return re.sub(r"[^A-Za-z0-9]", "", s)


def names_agree(function: str, program: str) -> bool:
    """``PjitFunction(_run_one)`` launches ``jit__run_one``."""
    return _alnum("jit" + function) == _alnum(program)


def link(launch_list: Sequence[Launch], execs: Sequence[Execution]
         ) -> List[Tuple[Execution, Optional[Launch]]]:
    """Each execution with the launch that started it (None where no launch
    is in the trace).  By ``run_id`` where the execution and a launch carry
    it; otherwise the executions of each device are paired in order with
    the launches left.  A pair whose names disagree raises: a trace must
    fail rather than put one layer's time under another."""
    by_id = {rid: la for la in launch_list for rid in la.run_ids}
    out: List[Tuple[Execution, Optional[Launch]]] = []
    used = set()
    rest: Dict[str, List[Execution]] = {}
    for x in execs:
        la = by_id.get(x.run_id) if x.run_id is not None else None
        if la is None:
            rest.setdefault(x.device, []).append(x)
            continue
        if not names_agree(la.function, x.program):
            raise ValueError(f"run {x.run_id}: launch {la.function!r} "
                             f"does not start program {x.program!r}")
        used.add(id(la))
        out.append((x, la))
    free = [la for la in launch_list if id(la) not in used]
    for dev, xs in rest.items():
        pending = list(free)
        for x in sorted(xs, key=lambda x: x.start):
            la = pending.pop(0) if pending else None
            if la is not None and not names_agree(la.function, x.program):
                raise ValueError(
                    f"{dev}: execution {x.program!r} at {x.start:.0f} ns "
                    f"pairs with launch {la.function!r}: launch order and "
                    f"execution order disagree")
            out.append((x, la))
    return sorted(out, key=lambda p: p[0].start)


def _clip(s: float, e: float, window) -> float:
    if window is None:
        return e - s
    return max(0.0, min(e, window[1]) - max(s, window[0]))


def span_device_seconds(pairs, window=None) -> Dict[str, float]:
    """Device seconds per innermost launching span (``no span`` for
    launches outside every program span, ``no launch`` for executions
    with none), clipped to ``window`` (ns)."""
    out: Dict[str, float] = {}
    for x, la in pairs:
        key = ("no launch" if la is None else
               la.span.name if la.span is not None else "no span")
        t = _clip(x.start, x.end, window) * 1e-9
        if t > 0:
            out[key] = out.get(key, 0.0) + t
    return out


# ------------------------------------------------------------ scope times

#: The protobuf fields read, numbered as the schemas number them:
#: ``XSpace`` (tsl/profiler/protobuf/xplane.proto), ``HloProto``
#: (xla/service/hlo.proto) and ``OpMetadata`` (xla/xla_data.proto).
#: ``test_perfbench_spans`` pins them against XLA's own reading of the HLO
#: protos of a recorded trace.
FIELD = {
    "XSpace.planes": 1, "XPlane.name": 2, "XPlane.event_metadata": 4,
    "XPlane.stat_metadata": 5, "map.key": 1, "map.value": 2,
    "XEventMetadata.name": 2, "XEventMetadata.stats": 5,
    "XStatMetadata.name": 2, "XStat.metadata_id": 1, "XStat.bytes_value": 6,
    "HloProto.hlo_module": 1, "HloModuleProto.computations": 3,
    "HloComputationProto.name": 1, "HloComputationProto.instructions": 2,
    "HloComputationProto.id": 5,
    "HloInstructionProto.name": 1, "HloInstructionProto.opcode": 2,
    "HloInstructionProto.metadata": 7,
    "HloInstructionProto.called_computation_ids": 38,
    "OpMetadata.op_name": 2,
}


def _varint(b, i: int) -> Tuple[int, int]:
    v = shift = 0
    while True:
        c = b[i]
        i += 1
        v |= (c & 0x7F) << shift
        if c < 0x80:
            return v, i
        shift += 7


def _fields(b):
    """(field number, value) of one protobuf message: ints for varints,
    memoryviews for length-delimited fields."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield num, v


def _get(msg, name: str) -> list:
    """Every value of field ``name`` (a key of :data:`FIELD`) in ``msg``."""
    num = FIELD[name]
    return [v for k, v in _fields(msg) if k == num]


def _text(msg, name: str, default: str = "") -> str:
    got = _get(msg, name)
    return bytes(got[-1]).decode() if got else default


def _packed(v) -> List[int]:
    if isinstance(v, int):
        return [v]
    out, i = [], 0
    while i < len(v):
        x, i = _varint(v, i)
        out.append(x)
    return out


#: HLO opcodes that only hold other ops: their events span their body's
#: ops, which the trace records (on the CPU, on other threads)
_CONTAINERS = ("while", "conditional", "call")


def hlo_module(hlo_proto) -> bytes:
    """The serialized ``HloModuleProto`` inside an ``HloProto``."""
    return bytes(_get(hlo_proto, "HloProto.hlo_module")[0])


class Instruction(NamedTuple):
    name: str
    opcode: str
    op_name: str                      # metadata.op_name, "" where none
    called: List[int]                 # ids of the computations it calls


def hlo_computations(hlo_proto) -> Dict[int, Tuple[str, List[Instruction]]]:
    """computation id -> (its name, its instructions) of an ``HloProto``."""
    comps = {}
    for comp in _get(hlo_module(hlo_proto), "HloModuleProto.computations"):
        instrs = []
        for v in _get(comp, "HloComputationProto.instructions"):
            meta = _get(v, "HloInstructionProto.metadata")
            instrs.append(Instruction(
                _text(v, "HloInstructionProto.name"),
                _text(v, "HloInstructionProto.opcode"),
                _text(meta[-1], "OpMetadata.op_name") if meta else "",
                [c for w in _get(
                    v, "HloInstructionProto.called_computation_ids")
                 for c in _packed(w)]))
        cid = (_get(comp, "HloComputationProto.id") or [0])[-1]
        comps[cid] = (_text(comp, "HloComputationProto.name"), instrs)
    return comps


def _hlo_op_names(hlo_proto) -> Dict[str, Tuple[str, str]]:
    """instruction name -> (``metadata.op_name``, opcode) of an
    ``HloProto``.  A fusion (or wrapped op) with no name of its own takes
    that of its fused computation's root, or of the op nearest the root
    that has one, so a fused op counts under the scope of its root; an op
    XLA inserted (a layout copy, a convert) takes the name of the op whose
    computation holds it."""
    comps = {cid: ins for cid, (_, ins) in
             hlo_computations(hlo_proto).items()}
    parent = {c: (i, cid) for cid, ins in comps.items() for i in ins
              for c in i[3]}

    def fused(ins, depth=0):
        if ins[2] or depth > 8:
            return ins[2]
        for c in ins[3]:
            # the root first, then the ops nearest it (post order)
            for j in reversed(comps.get(c, [])):
                got = fused(j, depth + 1)
                if got:
                    return got
        return ""

    def holder(cid, depth=0):
        if cid not in parent or depth > 16:
            return ""
        ins, up = parent[cid]
        return fused(ins) or holder(up, depth + 1)

    out = {}
    for cid, ins in comps.items():
        for i in ins:
            op = fused(i) or holder(cid)
            if op:
                out[i[0]] = (op, i[1])
    return out


def hlo_protos(xplane_path: str) -> Dict[str, bytes]:
    """``<module>(<program id>)`` -> its ``HloProto``, as the profiler
    stores them in the trace's ``/host:metadata`` plane."""
    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, bytes] = {}
    for plane in _get(space, "XSpace.planes"):
        if _text(plane, "XPlane.name") != "/host:metadata":
            continue
        stat_names = {}
        for entry in _get(plane, "XPlane.stat_metadata"):
            sid = _get(entry, "map.key")[0]
            stat_names[sid] = _text(_get(entry, "map.value")[0],
                                    "XStatMetadata.name")
        for entry in _get(plane, "XPlane.event_metadata"):
            meta = _get(entry, "map.value")[0]
            for st in _get(meta, "XEventMetadata.stats"):
                sid = (_get(st, "XStat.metadata_id") or [None])[0]
                blob = _get(st, "XStat.bytes_value")
                if stat_names.get(sid) == "Hlo Proto" and blob:
                    out[_text(meta, "XEventMetadata.name")] = bytes(blob[0])
    return out


def op_names(xplane_path: str) -> Dict[str, Dict[str, Tuple[str, str]]]:
    """``<module>(<program id>)`` -> instruction -> (op name, opcode), from
    the HLO protos in the trace."""
    return {m: _hlo_op_names(b) for m, b in hlo_protos(xplane_path).items()}


def hlo_op(name: str, stats: Dict[str, object]) -> str:
    """The HLO instruction of an op event: its ``hlo_op`` stat (the CPU),
    else the instruction's text the event is named by (a TPU's ``XLA Ops``
    line: ``%fusion.12 = bf16[...] fusion(...)``)."""
    if "hlo_op" in stats:
        return str(stats["hlo_op"])
    text = _HLO_TEXT.match(name)
    return text.group(1) if text else name


@dataclass
class OpEvent:
    op: str                           # hlo instruction name
    module: str                       # <module>(<program id>)
    path: Optional[str]               # op name path, scopes included
    start: float
    end: float
    line: str
    self_s: float = 0.0


def op_events(pd, names: Optional[Dict[str, dict]] = None
              ) -> List[OpEvent]:
    """The device's op events with their op-name paths, from the executed
    module's HLO metadata (``names``, from :func:`op_names`): neither the
    CPU's nor the TPU's op events carry the path themselves.  Container ops
    are left out; ``self_s`` is an event's time less the ops nested in it
    on the same line."""
    names = names or {}
    out: List[OpEvent] = []
    for plane in pd.planes:
        tpu = plane.name.startswith("/device:TPU:")
        if not tpu and plane.name != "/host:CPU":
            continue
        modules = []
        if tpu:
            for ln in plane.lines:
                if ln.name == "XLA Modules":
                    modules = sorted((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name.strip()) for e in ln.events)
        mstarts = [m[0] for m in modules]
        for ln in plane.lines:
            if tpu and ln.name != "XLA Ops":
                continue
            evs = []
            for e in ln.events:
                st = _stats(e)
                if not tpu and "hlo_op" not in st:
                    continue
                op = hlo_op(e.name, st)
                if "hlo_module" in st and "program_id" in st:
                    mod = f"{st['hlo_module']}({st['program_id']})"
                else:
                    i = bisect.bisect_right(mstarts, e.start_ns) - 1
                    mod = modules[i][2] if i >= 0 else ""
                path, code = names.get(mod, {}).get(op, (None, ""))
                if code in _CONTAINERS:
                    continue
                evs.append(OpEvent(op, mod, path, e.start_ns,
                                   e.start_ns + e.duration_ns,
                                   f"{plane.name}/{ln.name}"))
            evs.sort(key=lambda o: (o.start, -o.end))
            stack: List[OpEvent] = []
            for o in evs:
                o.self_s = (o.end - o.start) * 1e-9
                while stack and stack[-1].end <= o.start:
                    stack.pop()
                if stack and o.end <= stack[-1].end:
                    stack[-1].self_s -= (o.end - o.start) * 1e-9
                stack.append(o)
            out += evs
    return out


def scopes_of(path: Optional[str]) -> Tuple[str, ...]:
    """The words of an op-name path: ``jit(_run_one)/client_step/while/
    body/transpose(jvp(attn))/dot_general`` holds ``client_step`` and
    ``attn``; backward and recomputed ops keep their forward's scope."""
    return tuple(_WORD.findall(path)) if path else ()


@dataclass
class ScopeTimes:
    seconds: Dict[str, float]         # scope -> device seconds
    total_s: float                    # every op of the selected modules
    unpathed_s: float                 # ops whose path was not found
    remainder: Dict[str, float]       # ops under none of the scopes


def scope_device_seconds(ops: Sequence[OpEvent], scopes: Iterable[str],
                         window=None, within: Optional[str] = None
                         ) -> ScopeTimes:
    """Device seconds of the ops whose path holds each scope (a fused op
    counts under its root's path), over the ops of ``within`` (a scope
    every counted op must hold; None: all), clipped to ``window``."""
    scopes = tuple(scopes)
    secs = {s: 0.0 for s in scopes}
    total = unpathed = 0.0
    rest: Dict[str, float] = {}
    for o in ops:
        frac = 1.0
        if window is not None:
            dur = o.end - o.start
            frac = _clip(o.start, o.end, window) / dur if dur > 0 else 0.0
        t = o.self_s * frac
        if t <= 0:
            continue
        words = scopes_of(o.path)
        if within is not None and within not in words:
            if o.path is None:
                unpathed += t
            continue
        total += t
        hit = [s for s in scopes if s in words]
        for s in hit:
            secs[s] += t
        if not hit:
            key = re.sub(r"^jit\([^)]*\)/", "", o.path or f"? {o.op}")
            rest[key] = rest.get(key, 0.0) + t
    return ScopeTimes(secs, total, unpathed, rest)


# ------------------------------------------------------------- host time

def host_self_seconds(spans: Sequence[HostEvent], name: str,
                      children: Optional[Iterable[str]] = None,
                      window=None) -> float:
    """Seconds of the spans called ``name`` less the part that their child
    spans (on the same thread; ``children``: only spans of these names)
    cover."""
    kids = [s for s in spans if s.name != name and (
        children is None or s.name in set(children))]
    total = 0.0
    for p in spans:
        if p.name != name:
            continue
        lo, hi = p.start, p.end
        if window is not None:
            lo, hi = max(lo, window[0]), min(hi, window[1])
        if hi <= lo:
            continue
        inner = trace.union([(max(k.start, lo), min(k.end, hi))
                             for k in kids if k.line == p.line
                             and p.start <= k.start and k.end <= p.end
                             and k.end > lo and k.start < hi])
        total += (hi - lo) - sum(e - s for s, e in inner)
    return total * 1e-9


def named_gaps(pd, window, spans: Sequence[HostEvent], top: int = 10
               ) -> List[Tuple[str, float]]:
    """The ``top`` longest idle gaps of the device in ``window``, each named
    by :func:`innermost_label` at its middle."""
    out = []
    for evs in trace.device_events(pd).values():
        busy = trace.union([(max(s, window[0]), min(e, window[1]))
                            for _, s, e in evs
                            if e > window[0] and s < window[1]])
        out += trace.gaps(busy, window[0], window[1])
    out.sort(key=lambda g: g[0] - g[1])
    return [(innermost_label(spans, (s + e) / 2), (e - s) * 1e-9)
            for s, e in out[:top]]


# ------------------------------------------------------------- the report

def lines_used(spans: Sequence[HostEvent], execs: Sequence[Execution],
               ops: Sequence[OpEvent]) -> Dict[str, List[str]]:
    """The trace lines the reduction reads: the host threads that hold the
    ``parrot.*`` spans, and the device's program and op lines.  A trace
    that lacks one fails here, naming what is missing."""
    used = {"spans": sorted({s.line for s in spans}),
            "programs": sorted({x.device for x in execs}),
            "ops": sorted({o.line for o in ops})}
    missing = [
        what for what, key in (
            (f"host spans named {PREFIX}*", "spans"),
            ("device program executions (a TPU plane's 'XLA Modules' "
             "line, or the CPU's op events with a run_id)", "programs"),
            ("device op events (a TPU plane's 'XLA Ops' line, or the "
             "CPU's op events with an hlo_op)", "ops")) if not used[key]]
    if ops and not any(o.path for o in ops):
        missing.append("op-name paths: no op event's instruction is in "
                       "the HLO protos of the trace's /host:metadata plane")
    if missing:
        raise ValueError("the trace lacks " + "; ".join(missing))
    return used


def layers(pd, window, rounds: int,
           names: Optional[Dict[str, dict]] = None) -> dict:
    """The per-layer numbers of a traced run over ``window`` (ns) holding
    ``rounds`` rounds: device time by launching span and by scope, the
    engine's host time, the useful-step share and the idle gaps by
    phase."""
    events = host_events(pd)
    spans = [e for e in events if e.name.startswith(PREFIX)]
    execs = executions(pd)
    ops = op_events(pd, names)
    used = lines_used(spans, execs, ops)
    pairs = link(launches(pd, events), execs)
    inside = [(x, la) for x, la in pairs if _clip(x.start, x.end, window) > 0]
    by_span = span_device_seconds(inside, window)
    device_s = sum(by_span.values())
    step = scope_device_seconds(ops, STEP_SCOPES, window, "client_step")
    per = 1e3 / rounds

    def ms(*keys):
        t = sum(by_span.get(PREFIX + k, 0.0) for k in keys)
        return t * per if t > 0 else None

    counted = [s for s in spans if s.name == PREFIX + "client_step"
               and window[0] <= s.start <= window[1]]
    scanned = sum(int(s.stats.get("scanned", 0)) for s in counted)
    out = {
        "client_step.span_ms_per_round": ms("client_step"),
        "fold.span_ms_per_round": ms("fold"),
        "server.span_ms_per_round": ms("global_fold", "server_update"),
        "engine.host_ms_per_round": (
            host_self_seconds(spans, PREFIX + "round", LAUNCHING, window)
            * per if any(s.name == PREFIX + "round" for s in spans)
            else None),
        "client_step.useful_step_frac": (
            100.0 * sum(int(s.stats.get("steps", 0)) for s in counted)
            / scanned if scanned else None),
    }
    for scope, key in (("head_loss", "head"), ("attn", "attn"),
                       ("mlp", "mlp")):
        t = step.seconds.get(scope, 0.0)
        out[f"client_step.{key}_ms_per_round"] = t * per if t > 0 else None
    covered = sum(step.seconds.values())
    out["detail"] = {
        "lines_used": used,
        "executions": len(inside),
        "linked": sum(1 for _, la in inside if la is not None),
        "linked_by_run_id": sum(1 for x, la in inside if la is not None
                                and x.run_id in la.run_ids),
        "device_s_by_span": dict(sorted(by_span.items(),
                                        key=lambda kv: -kv[1])),
        "launching_share": (sum(by_span.get(k, 0.0) for k in LAUNCHING)
                            / device_s if device_s else None),
        "client_step_ops_s": step.total_s,
        "scope_s": step.seconds,
        "scope_share": covered / step.total_s if step.total_s else None,
        "ops_without_path_s": step.unpathed_s,
        "remainder_top": sorted(step.remainder.items(),
                                key=lambda kv: -kv[1])[:12],
        "idle_gaps": named_gaps(pd, window, events),
        "host_self_s": {s: host_self_seconds(spans, s, None, window)
                        for s in sorted({x.name for x in spans})},
    }
    return out


def record(server, rounds: int, directory: str) -> Tuple[str, float]:
    """Run ``rounds`` rounds under a profiler trace written to
    ``directory``, each round and its sync inside the benchmark's own
    spans (so :func:`trace.span_window` finds the window).  Returns the
    trace file and the rounds' wall seconds."""
    import time
    import jax
    from perfbench import harness
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with jax.profiler.trace(directory, profiler_options=opts):
        t0 = time.perf_counter()
        for _ in range(rounds):
            with jax.profiler.TraceAnnotation(harness.ROUND_SPAN):
                server.run_round()
            with jax.profiler.TraceAnnotation(harness.SYNC_SPAN):
                jax.block_until_ready(server.params)
        wall = time.perf_counter() - t0
    return trace.find_xplane(directory), wall


def reduce_trace(path: str, rounds: int) -> dict:
    """:func:`layers` of a trace file that :func:`record` wrote."""
    import jax
    from perfbench import harness
    pd = jax.profiler.ProfileData.from_file(path)
    return layers(pd, trace.span_window(pd, harness.ROUND_SPAN,
                                        harness.SYNC_SPAN),
                  rounds, op_names(path))


def main(argv=None) -> int:
    import argparse
    import json
    import shutil
    import tempfile
    import jax
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", default="")
    opts = ap.parse_args(argv)
    from perfbench import harness
    cell = harness.load_cell(opts.workload)
    server = harness.build(cell, opts.seed).server
    for _ in range(int(cell.traffic["reference_rounds"])):   # warm-up
        server.run_round()
    # as the benchmark's set-up ends: no warm-up work may still run on the
    # device when the trace starts, or it lands in the traced window
    jax.block_until_ready(server.params)
    rounds = int(cell.traffic["trace_rounds"])
    d = tempfile.mkdtemp(prefix="perfbench_spans_")
    try:
        path, wall = record(server, rounds, d)
        rep = reduce_trace(path, rounds)
    finally:
        shutil.rmtree(d, ignore_errors=True)
    rep["traced_round_s"] = wall / rounds
    text = json.dumps({"workload": opts.workload, "seed": opts.seed,
                       "layers": rep}, default=str)
    if opts.out:
        with open(opts.out, "a") as f:
            f.write(text + "\n")
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
