"""Reduce a profiler trace of the window to what the per-layer readers use.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``;
``jax.profiler.ProfileData`` reads it. The device planes are
``/device:TPU:<n>``, and their ``XLA Ops`` line holds one event per
operation run on the chip. The harness's own ``TraceAnnotation`` spans
(``window``, ``complete``, ``submit``, ``tick``, ``wait``) are events on the
host plane, on the same clock.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import os

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_SPANS = ("complete", "submit", "tick", "wait")


@dataclasses.dataclass
class Op:
    name: str                     # the HLO instruction, as the trace names it
    start_ns: float
    end_ns: float
    self_ns: float = 0.0          # less the ops nested in it (a loop's body)

    @property
    def dur_s(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9

    @property
    def short(self) -> str:
        """Instruction name and output type: ``fusion.117 = s32[8192,8]``."""
        return self.name.split("{", 1)[0][:60].lstrip("%")


def self_times(ops) -> None:
    """Set each op's self time: ops on one line nest (a while loop holds
    its body's ops), so subtract each op from the op that encloses it."""
    stack = []
    for op in sorted(ops, key=lambda o: (o.start_ns, -o.end_ns)):
        op.self_ns = op.end_ns - op.start_ns
        while stack and stack[-1].end_ns <= op.start_ns:
            stack.pop()
        if stack:
            stack[-1].self_ns -= op.end_ns - op.start_ns
        stack.append(op)


def merge(intervals):
    """Union of (start, end) intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def overlap(intervals, s, e) -> float:
    """Length of the part of [s, e) that disjoint sorted intervals cover."""
    i = max(0, bisect.bisect_right(intervals, [s]) - 1)
    total = 0.0
    while i < len(intervals) and intervals[i][0] < e:
        a, b = intervals[i]
        total += max(0.0, min(e, b) - max(s, a))
        i += 1
    return total


@dataclasses.dataclass
class Reduced:
    window: tuple                 # (start_ns, end_ns) of the traced window
    ops: list                     # Op that started in the window, chip 0
    busy: list                    # merged op intervals, first chip
    busy_s: float                 # averaged over the chips used
    host: dict                    # span name -> [(start_ns, end_ns)]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def n_ops(self) -> int:
        return len(self.ops)

    @property
    def n_complete(self) -> int:
        return len(self.host.get("complete", []))

    @property
    def busy_in_complete_s(self) -> float:
        return sum(overlap(self.busy, s, e)
                   for s, e in self.host.get("complete", [])) / 1e9

    def ops_matching(self, pred):
        return [op for op in self.ops if pred(op)]

    def gaps(self):
        """Idle stretches of the first chip inside the window."""
        t, out = self.window[0], []
        for s, e in self.busy:
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if t < self.window[1]:
            out.append((t, self.window[1]))
        return out

    def host_label(self, t: float) -> str:
        """The innermost harness span running on the host at time t (spans
        of one name follow each other on the one serving thread)."""
        best, label = None, "generator"
        for name in HOST_SPANS:
            spans = self.host.get(name, [])
            i = bisect.bisect_right(spans, (t, float("inf"))) - 1
            if i >= 0 and spans[i][1] > t and (best is None
                                               or spans[i][0] > best):
                best, label = spans[i][0], name
        return label

    def breakdown(self) -> dict:
        """Top device ops by self time, and idle time by what the host
        was doing."""
        by_op: dict = {}
        for op in self.ops:
            by_op[op.short] = by_op.get(op.short, 0.0) + op.self_ns / 1e9
        idle: dict = {}
        for s, e in self.gaps():
            label = self.host_label((s + e) / 2)
            idle[label] = idle.get(label, 0.0) + (e - s) / 1e9
        top = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in gaps]}


def xplane_file(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def reduce_file(path: str, n_chips: int = 1) -> Reduced:
    """Reduce one ``.xplane.pb`` file (or its gzip, ``.xplane.pb.gz``)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    host: dict = {}
    window = None
    devices = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            suffix = plane.name[len(DEVICE_PLANE):]
            if suffix.isdigit():
                devices.append((int(suffix), plane))
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "window":
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif ev.name in HOST_SPANS:
                    host.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    if window is None:
        raise ValueError(f"{path}: the harness's 'window' span is missing")
    w0, w1 = window
    host = {k: sorted((max(s, w0), min(e, w1)) for s, e in v
                      if e > w0 and s < w1)
            for k, v in host.items()}
    devices.sort(key=lambda d: d[0])
    ops0, busy0, busy_total = [], [], 0.0
    for n, (_, plane) in enumerate(devices[:n_chips]):
        ops = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if w0 <= s < w1:
                    ops.append(Op(ev.name, s, e))
        busy = merge((op.start_ns, min(op.end_ns, w1)) for op in ops)
        busy_total += sum(e - s for s, e in busy) / 1e9
        if n == 0:
            self_times(ops)
            ops0, busy0 = ops, busy
    n_used = max(1, min(n_chips, len(devices)))
    return Reduced(window=window, ops=ops0, busy=busy0,
                   busy_s=busy_total / n_used, host=host)


def reduce_trace(win, sys_, n_chips: int = 1) -> Reduced:
    return reduce_file(xplane_file(win.trace_dir), n_chips)
