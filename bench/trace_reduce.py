"""Reduce one profiler trace to device busy time, per-scope device time and
the ``breakdown`` of the result line.

Input is the ``*.trace.json.gz`` that ``jax.profiler.start_trace`` writes
next to the ``.xplane.pb``: its device ops carry the name stack XLA recorded
(``tf_op``), which the ``.xplane.pb`` reader of JAX does not expose.

What is read:

* device planes are the processes named ``/device:<PLATFORM>:<i>``; their
  ``XLA Ops`` thread holds one event per executed HLO op;
* those events nest: a ``while`` op spans the ops of its body. Each op's
  *self* time is its duration less that of the ops nested directly in it,
  so sums of self time never count a nanosecond twice;
* an op's scope is its ``tf_op``. Control ops that XLA records without one
  (``while``, ``conditional``) take the longest common prefix of the scopes
  nested in them, so a loop's own overhead lands in the layer of its body;
* a fusion carries the name stack of the op XLA kept as its root, so a
  fusion that spans two scopes is counted, whole, in the root's scope;
* the window is the host span ``bench_window``, ended at the close of the
  last call it covers: the exported trace stops at a cap of events
  (1,000,000), which in a window of many short calls cuts off the later
  ones. A call is covered where its execution of the call program (the
  ``jit_call`` module of ``bench/cell.py`` on the device's ``XLA Modules``
  line) holds as many ops as the fullest execution does; ``calls`` counts
  them, and per-call readings divide by it. The ops counted are those that
  start inside the window, and a device's busy time is the union of their
  intervals;
* idle gaps are the holes in that union, named by the host span (``bench_submit``,
  ``bench_fetch``, ``bench_key``) that covers the gap's middle once the
  device clock is aligned to the host's on the module launches.
"""

from __future__ import annotations

import bisect
import collections
import gzip
import json
import re
from dataclasses import dataclass, field
from pathlib import Path

HOST_SPANS = ("bench_submit", "bench_fetch", "bench_key")
#: The module name of the cell's call (``jax.jit`` of ``call`` in ``bench/cell.py``).
CALL_PROGRAM = "jit_call("

#: Layers by the scope an op runs in, first match wins.
LAYERS = (
    ("trace generation", lambda s: "bench_tracegen" in s),
    ("dispatch", lambda s: "bench_dispatch" in s),
    ("placement rule", lambda s: "bench_placement_rule" in s),
    ("scan engines", lambda s: "/while/body" in s),
)


def layer_of(scope: str) -> str:
    for name, pred in LAYERS:
        if pred(scope):
            return name
    return "outside the scans"


@dataclass
class Op:
    start: float          # us, device clock
    dur: float            # us
    name: str
    scope: str
    category: str
    self_us: float = 0.0
    children: list = field(default_factory=list)


@dataclass
class Trace:
    platform: str
    n_devices: int
    window_s: float
    busy_s: list                       # per device
    ops: list                          # [(device, Op)]
    gaps: list                         # [(host span name, seconds)]
    calls: int = 0                     # calls the trace covers on every device

    def self_seconds(self, pred) -> float:
        """Self time of the ops whose scope satisfies ``pred``, averaged
        over the devices."""
        tot = sum(op.self_us for _, op in self.ops if pred(op.scope))
        return tot * 1e-6 / max(self.n_devices, 1)

    def breakdown(self, layer_of, top: int = 10) -> dict:
        agg = collections.Counter()
        for _, op in self.ops:
            base = re.sub(r"[._]\d+$", "", op.name)
            tail = "/".join(p for p in op.scope.rstrip(":").split("/")[-2:] if p)
            agg[f"{layer_of(op.scope)}: {base} ({tail})"] += op.self_us
        dev = [[k, v * 1e-6 / max(self.n_devices, 1)]
               for k, v in agg.most_common(top)]
        gaps = collections.Counter()
        for name, sec in self.gaps:
            gaps[name] += sec
        idle = [[k, v / max(self.n_devices, 1)] for k, v in gaps.most_common(top)]
        return {"device_ops": dev, "idle_gaps": idle}


def _common_prefix(scopes: list) -> str:
    if not scopes:
        return ""
    parts = [s.split("/") for s in scopes]
    out = []
    for col in zip(*parts):
        if all(c == col[0] for c in col):
            out.append(col[0])
        else:
            break
    return "/".join(out) + ("/" if out else "")


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def find_trace_file(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).rglob("*.trace.json.gz"))
    if not files:
        raise FileNotFoundError(f"no *.trace.json.gz under {trace_dir}")
    return files[-1]


def reduce_trace(path: Path) -> Trace:
    """Read one ``*.trace.json.gz`` (or a directory holding one)."""
    path = Path(path)
    if path.is_dir():
        path = find_trace_file(path)
    with gzip.open(path, "rt") as fh:
        events = json.load(fh)["traceEvents"]

    procs, threads = {}, {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e["name"] == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        elif e["name"] == "thread_name":
            threads[(e["pid"], e["tid"])] = e["args"]["name"]
    dev_pids = {}
    for pid, name in procs.items():
        m = re.fullmatch(r"/device:([A-Za-z]+):(\d+)", name)
        if m:
            dev_pids[pid] = (m.group(1).lower(), int(m.group(2)))

    window = None
    host_spans = []
    launches = []
    dev_ops = collections.defaultdict(list)
    modules = collections.defaultdict(list)
    for e in events:
        if e.get("ph") != "X":
            continue
        pid, tname = e["pid"], threads.get((e["pid"], e.get("tid")), "")
        if pid in dev_pids:
            if tname == "XLA Ops":
                a = e.get("args", {})
                dev_ops[pid].append(Op(float(e["ts"]), float(e["dur"]), e["name"],
                                       a.get("tf_op", ""), a.get("hlo_category", "")))
            elif tname == "XLA Modules":
                modules[pid].append((float(e["ts"]), float(e["ts"]) + float(e["dur"]),
                                     e["name"]))
        elif procs.get(pid, "").startswith("/host"):
            if e["name"] == "bench_window":
                window = (float(e["ts"]), float(e["ts"]) + float(e["dur"]))
            elif e["name"] in HOST_SPANS:
                host_spans.append((float(e["ts"]), float(e["ts"]) + float(e["dur"]),
                                   e["name"]))
            elif e["name"] == "tpu::System::Execute" and tname.startswith("main"):
                launches.append(float(e["ts"]))
    if not dev_pids:
        raise ValueError(f"{path}: no device plane")

    # Align the device clock to the host's on the module launches.
    offsets = []
    for pid in dev_pids:
        for (s, _, _), h in zip(sorted(modules[pid]), sorted(launches)):
            offsets.append(h - s)
    offset = sorted(offsets)[len(offsets) // 2] if offsets else 0.0
    if window is None:                 # a trace taken without the harness
        spans = [m for pid in dev_pids for m in modules[pid]]
        window = (min(s for s, _, _ in spans) + offset,
                  max(e for _, e, _ in spans) + offset)

    covered = {pid: _covered_calls(modules[pid], dev_ops[pid], window[0] - offset,
                                   window[1] - offset) for pid in dev_pids}
    calls = 0
    if all(covered.values()):
        window = (window[0], min(window[1], min(c[-1][1] for c in covered.values()) + offset))
        calls = min(sum(e + offset <= window[1] for _, e in c) for c in covered.values())

    ops_all, busy, gaps = [], [], []
    for pid in sorted(dev_pids, key=lambda p: dev_pids[p][1]):
        ops = [o for o in dev_ops[pid]
               if window[0] <= o.start + offset <= window[1]]
        _set_nesting(ops)
        ops_all.extend((dev_pids[pid][1], o) for o in ops)
        spans = _union([(o.start, o.start + o.dur) for o in ops])
        busy.append(sum(e - s for s, e in spans) * 1e-6)
        for (_, e0), (s1, _) in zip(spans, spans[1:]):
            mid = 0.5 * (e0 + s1) + offset
            name = "host, no bench span"
            for hs, he, hn in host_spans:
                if hs <= mid <= he:
                    name = hn
            gaps.append((name, (s1 - e0) * 1e-6))
    platform = next(iter(dev_pids.values()))[0]
    return Trace(platform=platform, n_devices=len(dev_pids),
                 window_s=(window[1] - window[0]) * 1e-6, busy_s=busy,
                 ops=ops_all, gaps=gaps, calls=calls)


def _covered_calls(modules: list, ops: list, lo: float, hi: float) -> list:
    """(start, end), in order, of the call program's executions that start in
    ``[lo, hi]`` (device clock) and hold as many ops as the fullest of them:
    every execution of one program holds the same ops, and one that the
    trace's cap cut holds fewer."""
    starts = sorted(o.start for o in ops)
    runs = [(s, e, bisect.bisect_right(starts, e) - bisect.bisect_left(starts, s))
            for s, e, name in sorted(modules)
            if name.startswith(CALL_PROGRAM) and lo <= s <= hi]
    full = max((n for _, _, n in runs), default=0)
    return [(s, e) for s, e, n in runs if n == full > 0]


def _set_nesting(ops: list) -> None:
    """Link each op to the op it runs inside; fill self times and scopes."""
    ops.sort(key=lambda o: (o.start, -o.dur))
    stack: list = []
    roots = []
    for op in ops:
        while stack and op.start >= stack[-1].start + stack[-1].dur - 1e-3:
            stack.pop()
        (stack[-1].children if stack else roots).append(op)
        stack.append(op)

    def fill(op: Op) -> str:
        kids = [fill(c) for c in op.children]
        op.self_us = max(op.dur - sum(c.dur for c in op.children), 0.0)
        if not op.scope:
            op.scope = _common_prefix([k for k in kids if k])
        return op.scope

    for r in roots:
        fill(r)
