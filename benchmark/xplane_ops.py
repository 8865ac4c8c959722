"""The device ops of a profiler trace (`.xplane.pb`) with their op
metadata, which jax.profiler.ProfileData does not expose, and the
numbers the benchmark reads from them beside trace_reduce's.

The reader decodes the protobuf wire format of tsl's `xplane.proto`
(XSpace > XPlane > XLine > XEvent, and each plane's event and stat
metadata). On a device plane every "XLA Ops" event's metadata carries
a `tf_op` stat: the JAX op_name path of the op, such as
`jit(_consensus_fused)/fame/while/body/eq`. The fused consensus kernel
names its stages with `jax.named_scope` (ops/incremental.py), so the
first path element after `jit(_consensus_fused)/` is the stage.

- `fused_stages_s`: leaf-op device time inside `jit__consensus_fused`
  modules, by stage. A leaf op encloses no other op (a `while` op
  encloses its body's ops). An op with an empty `tf_op` takes the stage
  of its innermost enclosing op; any other op is `unscoped`.
- `idle_in_span_s`: device-idle time in the window that lies under
  each `engine.*` host span (the engine's own spans), with no minimum
  gap, summed per span name. Nested spans each count their own.
- `redo_spans`: `engine.redo` spans that start in the window.

Times are averaged over the device planes, as in trace_reduce. A trace
with no device plane (the CPU backend) reads empty.

    python3 benchmark/xplane_ops.py <file.xplane.pb[.xz]>
"""

from __future__ import annotations

import bisect
import json
import lzma
import sys
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

if __package__ in (None, ""):
    import os

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmark.trace_reduce import WINDOW_SPAN, clip, gaps, kernel_name, union

FUSED = "_consensus_fused"
STAGES = ("frontier", "rounds", "fame", "round_received", "timestamps",
          "pack")
UNSCOPED = "unscoped"
ENGINE_PREFIX = "engine."
REDO_SPAN = "engine.redo"

# Field numbers of tsl/profiler/protobuf/xplane.proto.
SPACE_PLANES = 1
PLANE_NAME, PLANE_LINES, PLANE_EVENT_META, PLANE_STAT_META = 2, 3, 4, 5
LINE_NAME, LINE_TIMESTAMP_NS, LINE_EVENTS = 2, 3, 4
EVENT_META_ID, EVENT_OFFSET_PS, EVENT_DURATION_PS = 1, 2, 3
META_ID, META_NAME, META_STATS = 1, 2, 5
STAT_META_ID, STAT_STR, STAT_REF = 1, 5, 7
MAP_KEY, MAP_VALUE = 1, 2


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes):
    """(field number, value) of one message: an int for varint and
    fixed fields, a memoryview for length-delimited ones."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 1:
            val, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            val, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, val


@dataclass
class Plane:
    name: str
    # line name -> [(start_ns, end_ns, metadata id)], in file order
    lines: Dict[str, List[Tuple[float, float, int]]] = field(
        default_factory=dict)
    names: Dict[int, str] = field(default_factory=dict)  # metadata id -> name
    tf_op: Dict[int, str] = field(default_factory=dict)  # metadata id -> path


def _plane(buf) -> Plane:
    plane = Plane(name="")
    stat_names: Dict[int, str] = {}
    metas = []
    for f, v in _fields(buf):
        if f == PLANE_NAME:
            plane.name = bytes(v).decode()
        elif f == PLANE_LINES:
            name, ts, events = "", 0, []
            for lf, lv in _fields(v):
                if lf == LINE_NAME:
                    name = bytes(lv).decode()
                elif lf == LINE_TIMESTAMP_NS:
                    ts = lv
                elif lf == LINE_EVENTS:
                    events.append(lv)
            out = plane.lines.setdefault(name, [])
            for ev in events:
                mid = off = dur = 0
                for ef, evv in _fields(ev):
                    if ef == EVENT_META_ID:
                        mid = evv
                    elif ef == EVENT_OFFSET_PS:
                        off = evv
                    elif ef == EVENT_DURATION_PS:
                        dur = evv
                start = ts + off / 1000
                out.append((start, start + dur / 1000, mid))
        elif f == PLANE_EVENT_META:
            metas.append(dict(_fields(v)).get(MAP_VALUE, b""))
        elif f == PLANE_STAT_META:
            entry = dict(_fields(v))
            meta = dict(_fields(entry.get(MAP_VALUE, b"")))
            stat_names[entry.get(MAP_KEY, 0)] = bytes(
                meta.get(META_NAME, b"")).decode()
    tf_op_id = next((k for k, n in stat_names.items() if n == "tf_op"), None)
    for meta in metas:
        mid, name = 0, ""
        for mf, mv in _fields(meta):
            if mf == META_ID:
                mid = mv
            elif mf == META_NAME:
                name = bytes(mv).decode()
            elif mf == META_STATS and tf_op_id is not None:
                stat = dict(_fields(mv))
                if stat.get(STAT_META_ID) == tf_op_id:
                    if STAT_STR in stat:
                        plane.tf_op[mid] = bytes(stat[STAT_STR]).decode()
                    elif STAT_REF in stat:
                        plane.tf_op[mid] = stat_names.get(stat[STAT_REF], "")
        plane.names[mid] = name
    return plane


def read_planes(path: str) -> List[Plane]:
    """Every plane of an XSpace file (xz-compressed if it ends in .xz)."""
    opener = lzma.open if path.endswith(".xz") else open
    with opener(path, "rb") as fh:
        buf = fh.read()
    return [_plane(v) for f, v in _fields(buf) if f == SPACE_PLANES]


def stage_of(tf_op: str) -> str:
    """`jit(_consensus_fused)/fame/while/body/eq` -> `fame`."""
    head = f"jit({FUSED})/"
    if not tf_op.startswith(head):
        return UNSCOPED
    stage = tf_op[len(head):].split("/", 1)[0]
    return stage if stage in STAGES else UNSCOPED


def leaf_stages(ops, tf_op: Dict[int, str]):
    """(start, end, stage) of each leaf op: an op that encloses no other
    op. An op with an empty tf_op takes its innermost enclosing op's
    stage."""
    out = []
    stack: List[list] = []  # [end, stage, is_leaf, start]
    for s, e, mid in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= s:
            top = stack.pop()
            if top[2]:
                out.append((top[3], top[0], top[1]))
        path = tf_op.get(mid, "")
        if path:
            stage = stage_of(path)
        else:
            stage = stack[-1][1] if stack else UNSCOPED
        if stack:
            stack[-1][2] = False
        stack.append([e, stage, True, s])
    out.extend((top[3], top[0], top[1]) for top in stack if top[2])
    return out


def overlap(idle: List[Tuple[float, float]], starts: List[float],
            s: float, e: float) -> float:
    """Length of [s, e) covered by the sorted, disjoint `idle`."""
    total = 0.0
    k = max(bisect.bisect_right(starts, s) - 1, 0)
    while k < len(idle) and idle[k][0] < e:
        total += max(0.0, min(e, idle[k][1]) - max(s, idle[k][0]))
        k += 1
    return total


@dataclass
class TraceOps:
    window_s: float
    devices: int
    fused_module_s: float = 0.0  # jit__consensus_fused modules, in window
    fused_stages_s: Dict[str, float] = field(default_factory=dict)
    idle_in_span_s: Dict[str, float] = field(default_factory=dict)
    redo_spans: int = 0


def summarize(path: str) -> TraceOps:
    planes = read_planes(path)
    spans = []
    for p in planes:
        if p.name.startswith("/device:"):
            continue
        for events in p.lines.values():
            for s, e, mid in events:
                name = p.names.get(mid, "")
                if name == WINDOW_SPAN or name.startswith(ENGINE_PREFIX):
                    spans.append((name, s, e))
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not win:
        raise ValueError(f"{path}: no {WINDOW_SPAN} span in the trace")
    lo, hi = min(s for s, _ in win), max(e for _, e in win)
    engine = sorted((s, e, n) for n, s, e in spans if n != WINDOW_SPAN)
    devs = [p for p in planes if p.name.startswith("/device:")
            and "XLA Ops" in p.lines]
    nd = max(len(devs), 1)
    out = TraceOps(window_s=(hi - lo) / 1e9, devices=len(devs),
                   redo_spans=sum(1 for s, _, n in engine
                                  if n == REDO_SPAN and lo <= s < hi))
    stages: Dict[str, float] = {}
    idle_ns: Dict[str, float] = {}
    module_ns = 0.0
    for p in devs:
        ops = p.lines["XLA Ops"]
        mods = sorted((s, e) for s, e, mid in p.lines.get("XLA Modules", [])
                      if kernel_name(p.names.get(mid, "")) == FUSED)
        module_ns += sum(e - s for s, e in clip(mods, lo, hi))
        starts = [s for s, _ in mods]
        for s, e, stage in leaf_stages(ops, p.tf_op):
            k = bisect.bisect_right(starts, s) - 1
            if k < 0 or e > mods[k][1]:
                continue  # not inside a fused module
            for cs, ce in clip([(s, e)], lo, hi):
                stages[stage] = stages.get(stage, 0.0) + (ce - cs)
        busy = union(clip([(s, e) for s, e, _ in ops], lo, hi))
        idle = gaps(busy, lo, hi)
        idle_starts = [s for s, _ in idle]
        for s, e, name in engine:
            ns = overlap(idle, idle_starts, max(s, lo), min(e, hi))
            if ns > 0:
                idle_ns[name] = idle_ns.get(name, 0.0) + ns
    out.fused_module_s = module_ns / nd / 1e9
    out.fused_stages_s = {k: v / nd / 1e9 for k, v in stages.items()}
    out.idle_in_span_s = {k: v / nd / 1e9 for k, v in idle_ns.items()}
    return out


if __name__ == "__main__":
    print(json.dumps(summarize(sys.argv[1]).__dict__, indent=1))
