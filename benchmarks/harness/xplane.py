"""From the profiler's `.xplane.pb` to the numbers the metrics read.

What a v5e trace looks like (read by hand first, PR 23): one plane per chip,
`/device:TPU:<n>`, whose line `XLA Ops` holds every HLO instruction the core
ran, named by its full HLO text, NESTED (a `while` spans the instructions of
its body), with start and duration in ns on the same clock as the host
plane `/host:CPU`, whose `python3` lines hold the `TraceAnnotation`s the
harness's wrappers write (`bench:<what>`) and the profiler's own
`start_trace` / `stop_trace` calls. A compiled Pallas kernel is a
`custom-call` with `custom_call_target="tpu_custom_call"`.

Reduction, per chip and then averaged over the chips:
  window   from the end of `start_trace` to the start of `stop_trace` on the
           host plane (else first to last device instruction)
  busy     the union of the `XLA Ops` intervals inside the window
  op time  SELF time of each instruction (its duration less its children's),
           so a loop is not counted on top of its body
  gaps     the complement of busy, each named after the `bench:` annotation
           that covers most of it ("unattributed" when none does)
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

    def op_seconds(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(s for label, s in self.op_self_s.items() if rx.search(label))

    def op_calls(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(n for label, n in self.op_calls_n.items() if rx.search(label))

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


def reduce_planes(planes: list) -> TraceSummary | None:
    """`planes`: [(plane name, [(line name, [(start_ns, dur_ns, name)])])]."""
    host_marks, annotations, devices = {}, [], []
    for pname, lines in planes:
        if pname == "/host:CPU":
            for _, events in lines:
                for s, d, name in events:
                    if name.endswith(" start_trace"):
                        host_marks["lo"] = s + d
                    elif name.endswith(" stop_trace"):
                        host_marks["hi"] = s
                    elif name.startswith("bench:"):
                        annotations.append((s, s + d, name))
        elif _DEVICE.match(pname):
            for lname, events in lines:
                if lname == "XLA Ops":
                    devices.append([(s, s + d, name) for s, d, name in events])
    devices = [d for d in devices if d]
    if not devices:
        return None
    lo = host_marks.get("lo", min(ev[0] for d in devices for ev in d))
    hi = host_marks.get("hi", max(ev[1] for d in devices for ev in d))
    if hi <= lo:
        return None
    busy_ns, op_ns, op_n, gaps = 0.0, {}, {}, {}
    for k, events in enumerate(devices):
        inside = list(_clip(events, lo, hi))
        busy = _union((s, e) for s, e, _ in inside)
        busy_ns += sum(e - s for s, e in busy)
        for hlo, (ns, n) in _self_times(inside).items():
            label = label_of(hlo)
            op_ns[label] = op_ns.get(label, 0.0) + ns
            op_n[label] = op_n.get(label, 0) + n
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
        gaps=[(k, v / 1e9) for k, v in gaps.items()])


def load_planes(path: str) -> list:
    """Reads an `.xplane.pb` (or `.xplane.pb.gz`) with nothing but jax."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    return [(plane.name, [(line.name, [(ev.start_ns, ev.duration_ns, ev.name)
                                       for ev in line.events])
                          for line in plane.lines])
            for plane in data.planes]


def reduce_trace_dir(trace_dir: str) -> TraceSummary | None:
    """The newest trace under a `jax.profiler.start_trace` directory."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return reduce_planes(load_planes(found[-1])) if found else None
