"""From the profiler's `.xplane.pb` to the numbers the metrics read.

What a v5e trace looks like (read by hand first, PR 23): one plane per chip,
`/device:TPU:<n>`, whose line `XLA Ops` holds every HLO instruction the core
ran, named by its full HLO text, NESTED (a `while` spans the instructions of
its body), with start and duration in ns on the same clock as the host
plane `/host:CPU`, whose `python3` lines hold the `TraceAnnotation`s the
program writes around each step of its loops (`engine:admit`, `engine:decode`,
and inside them `engine:<step>.<phase>`; the train loop's is the harness's own
`bench:train_step`) and the profiler's own `start_trace` / `stop_trace`
calls. A compiled Pallas kernel is a `custom-call` with
`custom_call_target="tpu_custom_call"`, named by the kernel's `name=`. The
text leaves the instruction's metadata out; its `op_name`, which holds the
`jax.named_scope`s it was traced under (`jit(decode)/while/body/attn/kv_write/
dynamic_update_slice`), is the `tf_op` stat of the event's metadata in the
file, which `jax.profiler.ProfileData` does not hand out: `load_op_names`
reads that one table from the file's bytes.

Reduction, per chip and then averaged over the chips:
  window   from the end of `start_trace` to the start of `stop_trace` on the
           host plane (else first to last device instruction)
  busy     the union of the `XLA Ops` intervals inside the window
  op time  SELF time of each instruction (its duration less its children's),
           so a loop is not counted on top of its body
  scopes   the same self time by the instruction's `op_name`: `scope_seconds`
           matches a pattern anywhere in that path, so an instruction counts
           under every scope it was traced in
  gaps     the complement of busy, each named after the step annotation
           (`engine:<step>` or `bench:<step>`, not a phase inside one) that
           covers most of it ("unattributed" when none does)
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import os
import re

_DEVICE = re.compile(r"^/device:TPU:\d+$")
_BRACES = re.compile(r"\{[^{}]*\}")
_INSTR = re.compile(r"^%?(\S+) = (.+?) ([a-z][\w\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_STEP_ANNOTATION = re.compile(r"^(engine|bench):[^.]+$")


def label_of(hlo: str) -> str:
    """`name kind[target] shape` of an instruction from its HLO text: what
    patterns are matched against and what a breakdown prints. Operands are
    left out, so a pattern never matches an instruction for what it reads."""
    flat = hlo
    while True:
        nxt = _BRACES.sub("", flat)
        if nxt == flat:
            break
        flat = nxt
    m = _INSTR.match(flat)
    if not m:
        return hlo.split(" = ")[0].lstrip("%")[:80]
    name, shape, kind = m.groups()
    target = _TARGET.search(hlo)
    if target:
        kind = f"{kind}[{target.group(1)}]"
    return f"{name} {kind} {shape}"[:96]


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                 # averaged over the chips
    n_chips: int
    op_self_s: dict               # label -> self seconds, averaged over chips
    op_calls_n: dict              # label -> calls, averaged over chips
    gaps: list                    # [(name, seconds)] summed by name, chip 0
    scope_self_s: dict = dataclasses.field(default_factory=dict)  # op_name -> self seconds

    def op_seconds(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(s for label, s in self.op_self_s.items() if rx.search(label))

    def op_calls(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(n for label, n in self.op_calls_n.items() if rx.search(label))

    def scope_seconds(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(s for path, s in self.scope_self_s.items() if rx.search(path))

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.op_self_s.items(), key=lambda kv: -kv[1])[:n]
        gaps = sorted(self.gaps, key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def _clip(events, lo, hi):
    for s, e, name in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield s, e, name


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _self_times(events):
    """{label: [self ns, calls]} of nested (start, end, name) events."""
    acc: dict = {}
    stack: list = []   # [end, name, child_ns, dur]

    def close(item):
        rec = acc.setdefault(item[1], [0.0, 0])
        rec[0] += max(item[3] - item[2], 0.0)
        rec[1] += 1

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -(ev[1] - ev[0]))):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] += e - s
        stack.append([e, name, 0.0, e - s])
    while stack:
        close(stack.pop())
    return acc


def reduce_planes(planes: list, op_names: dict | None = None) -> TraceSummary | None:
    """`planes`: [(plane name, [(line name, [(start_ns, dur_ns, name)])])];
    `op_names`: {plane name: {event name: op_name}} (`load_op_names`)."""
    host_marks, annotations, devices = {}, [], []
    for pname, lines in planes:
        if pname == "/host:CPU":
            for _, events in lines:
                for s, d, name in events:
                    if name.endswith(" start_trace"):
                        host_marks["lo"] = s + d
                    elif name.endswith(" stop_trace"):
                        host_marks["hi"] = s
                    elif _STEP_ANNOTATION.match(name):
                        annotations.append((s, s + d, name))
        elif _DEVICE.match(pname):
            for lname, events in lines:
                if lname == "XLA Ops" and events:
                    devices.append(([(s, s + d, name) for s, d, name in events],
                                    (op_names or {}).get(pname, {})))
    if not devices:
        return None
    lo = host_marks.get("lo", min(ev[0] for d, _ in devices for ev in d))
    hi = host_marks.get("hi", max(ev[1] for d, _ in devices for ev in d))
    if hi <= lo:
        return None
    busy_ns, op_ns, op_n, scope_ns, gaps = 0.0, {}, {}, {}, {}
    for k, (events, scope_of) in enumerate(devices):
        inside = list(_clip(events, lo, hi))
        busy = _union((s, e) for s, e, _ in inside)
        busy_ns += sum(e - s for s, e in busy)
        for hlo, (ns, n) in _self_times(inside).items():
            label = label_of(hlo)
            op_ns[label] = op_ns.get(label, 0.0) + ns
            op_n[label] = op_n.get(label, 0) + n
            if hlo in scope_of:
                scope_ns[scope_of[hlo]] = scope_ns.get(scope_of[hlo], 0.0) + ns
        if k == 0:
            edges = [lo] + [t for iv in busy for t in iv] + [hi]
            for gs, ge in zip(edges[0::2], edges[1::2]):
                if ge <= gs:
                    continue
                best, cover = "unattributed", 0.0
                for s, e, name in annotations:
                    c = min(e, ge) - max(s, gs)
                    if c > cover:
                        best, cover = name, c
                gaps[best] = gaps.get(best, 0.0) + (ge - gs)
    n = len(devices)
    return TraceSummary(
        window_s=(hi - lo) / 1e9, busy_s=busy_ns / n / 1e9, n_chips=n,
        op_self_s={k: v / n / 1e9 for k, v in op_ns.items()},
        op_calls_n={k: v / n for k, v in op_n.items()},
        gaps=[(k, v / 1e9) for k, v in gaps.items()],
        scope_self_s={k: v / n / 1e9 for k, v in scope_ns.items()})


def _read(path: str) -> bytes:
    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        return f.read()


def load_planes(path: str, raw: bytes | None = None) -> list:
    """Reads an `.xplane.pb` (or `.xplane.pb.gz`) with nothing but jax."""
    from jax.profiler import ProfileData

    data = ProfileData.from_serialized_xspace(raw or _read(path))
    return [(plane.name, [(line.name, [(ev.start_ns, ev.duration_ns, ev.name)
                                       for ev in line.events])
                          for line in plane.lines])
            for plane in data.planes]


def _fields(buf):
    """(field number, wire type, value) of one protobuf message: a varint as
    an int, a length-delimited field as a memoryview, fixed ones as bytes."""
    i, n = 0, len(buf)

    def varint():
        nonlocal i
        value = shift = 0
        while True:
            b = buf[i]
            i += 1
            value |= (b & 0x7F) << shift
            if b < 0x80:
                return value
            shift += 7

    while i < n:
        key = varint()
        wire = key & 7
        if wire == 0:
            value = varint()
        elif wire == 2:
            size = varint()
            value, i = buf[i:i + size], i + size
        else:
            size = {1: 8, 5: 4}[wire]
            value, i = bytes(buf[i:i + size]), i + size
        yield key >> 3, wire, value


def load_op_names(path: str, raw: bytes | None = None) -> dict:
    """{plane name: {event name: op_name}} of the device planes, from the
    file's bytes. In `xplane.proto` an XSpace holds planes (field 1); an
    XPlane its name (2) and two maps, event metadata (4) and stat metadata
    (5), whose entries are (key 1, value 2); an XEventMetadata its name (2)
    and stats (5); an XStat its metadata id (1) and a string (5) or a
    reference to a stat metadata's name (7); an XStatMetadata its name (2).
    The stat named `tf_op` is `<op_name>:<op type>`."""
    def text(view) -> str:
        return bytes(view).decode()

    out = {}
    for f, _, plane in _fields(memoryview(raw or _read(path))):
        if f != 1:
            continue
        pname, events, stat_names = "", [], {}
        for f, _, v in _fields(plane):
            if f == 2:
                pname = text(v)
            elif f == 4:
                events.append(dict((k, x) for k, _, x in _fields(v))[2])
            elif f == 5:
                entry = dict((k, x) for k, _, x in _fields(v))
                stat_names[entry[1]] = next(
                    (text(x) for k, _, x in _fields(entry[2]) if k == 2), "")
        if not _DEVICE.match(pname):
            continue
        names = out.setdefault(pname, {})
        for event in events:
            ename = op = None
            for f, _, v in _fields(event):
                if f == 2:
                    ename = text(v)
                elif f == 5:
                    stat = dict((k, x) for k, _, x in _fields(v))
                    if stat_names.get(stat.get(1)) == "tf_op":
                        op = text(stat[5]) if 5 in stat else stat_names.get(stat.get(7))
            if ename and op:
                names.setdefault(ename, op.rpartition(":")[0] or op)
    return out


def reduce_file(path: str) -> TraceSummary | None:
    """One trace file, read once: its events and its `op_name`s."""
    raw = _read(path)
    return reduce_planes(load_planes(path, raw), load_op_names(path, raw))


def reduce_trace_dir(trace_dir: str) -> TraceSummary | None:
    """The newest trace under a `jax.profiler.start_trace` directory."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return reduce_file(found[-1]) if found else None
