"""The profiled stretch of a traced run, and its reduction to numbers.

`Window` opens a `jax.profiler` trace around a stretch of the measured
window, marks it with a host annotation (``bench.window``), and turns the
recorded trace into a small normalized record:

    {"window": [start_ns, end_ns],
     "devices": {plane: [[label, start_ns, dur_ns, opcode], ...]},
     "host": [[name, start_ns, dur_ns], ...],
     "counters": {...}}

``host`` holds, from the trace's host plane and on the profiler's own
clock, the benchmark's annotations (``bench.*``) and every span of the
program's telemetry (`Telemetry.span`, which is also a profiler
annotation of its name) that the program opened or closed in the window.
``counters`` holds the window's change of the server's three slot
counters and of every counter of the program's telemetry.  `reduce`
reads only this record, so the committed sample traces
(``bench/trace_sample/``) check the same arithmetic the runs use.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import itertools
import json
import os
import shutil
import tempfile

import jax

BENCH_PREFIX = "bench."  # the benchmark's own annotations
WINDOW = "bench.window"  # also the label of idle time no other span covers


class Window:
    def __init__(self, trace_dir, server):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_", dir=trace_dir)
        self.server = server
        self.active = False
        self.record = None

    def _counters(self) -> dict:
        srv = self.server
        return dict(srv.telemetry.metrics_snapshot()["counters"],
                    busy_slot_sweeps=srv.busy_slot_sweeps,
                    total_slot_sweeps=srv.total_slot_sweeps, launches=srv.launches)

    def start(self) -> None:
        srv = self.server
        jax.profiler.start_trace(self.dir)
        self.ann = jax.profiler.TraceAnnotation(WINDOW)
        self.ann.__enter__()
        self.tel0 = srv.telemetry.now_us()
        self.c0 = self._counters()
        self.active = True

    def stop(self) -> None:
        srv = self.server
        jax.block_until_ready(srv.carry)
        c1 = self._counters()
        tel1 = srv.telemetry.now_us()
        self.ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False
        names = {ev["name"] for ev in srv.telemetry.events()
                 if ev.get("ph") in ("B", "E") and self.tel0 <= ev["ts"] <= tel1}
        try:
            self.record = normalize(self.dir, names)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        self.record["counters"] = {k: v - self.c0.get(k, 0) for k, v in c1.items()}

    def reduce(self) -> dict:
        return reduce(self.record)


def opcode(hlo: str) -> str:
    """The HLO opcode of an op's text (``%x = <shape> <opcode>(...)``);
    a Pallas kernel reads as its custom-call target."""
    if 'custom_call_target="tpu_custom_call"' in hlo:
        return "tpu_custom_call"
    rest = hlo.split(" = ", 1)[-1]
    if rest.startswith("("):  # a tuple shape
        depth = 0
        for k, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[k + 1:]
                break
    else:
        rest = rest.split(" ", 1)[-1]
    return rest.strip().split("(", 1)[0] or "op"


def _device_ops(lines: dict) -> list:
    """[label, start_ns, dur_ns, opcode] of a device plane's XLA ops; the
    label names the program (XLA module) the op ran in and its opcode."""
    mods = sorted((int(m.start_ns), int(m.start_ns + m.duration_ns), m.name.split("(")[0])
                  for m in (lines["XLA Modules"].events if "XLA Modules" in lines else []))
    starts = [m[0] for m in mods]
    out = []
    for ev in lines["XLA Ops"].events:
        t = int(ev.start_ns)
        k = bisect.bisect_right(starts, t) - 1
        module = mods[k][2] if k >= 0 and t < mods[k][1] else "?"
        op = opcode(ev.name)
        out.append([f"{module} {op}", t, int(ev.duration_ns), op])
    return out


def normalize(logdir: str, program_spans) -> dict:
    """Read the newest xplane under ``logdir`` into the normalized record,
    keeping from the host plane the spans named in ``program_spans`` and
    the benchmark's annotations."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise RuntimeError(f"no profiler trace was written under {logdir}")
    pd = ProfileData.from_file(paths[-1])
    program_spans = set(program_spans)
    window, host, devices = None, [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" in lines:
                devices[plane.name] = _device_ops(lines)
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name == WINDOW:
                        window = [int(ev.start_ns), int(ev.start_ns + ev.duration_ns)]
                    elif ev.name.startswith(BENCH_PREFIX) or ev.name in program_spans:
                        host.append([ev.name, int(ev.start_ns), int(ev.duration_ns)])
    if window is None:
        raise RuntimeError(f"the trace holds no {WINDOW} annotation")
    for evs in devices.values():
        evs[:] = [e for e in evs if e[1] + e[2] > window[0] and e[1] < window[1]]
    return dict(window=window, devices=devices, host=host)


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap(a: list, b: list) -> int:
    """Length of the intersection of two sorted lists of disjoint intervals."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        total += max(0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


class _Innermost:
    """Which of some host spans is the innermost that covers an instant:
    of those that cover it, the one that opened last (a nested span opens
    inside the span that holds it)."""

    def __init__(self, spans: list):
        self.spans = sorted(((s, s + d, name) for name, s, d in spans),
                            key=lambda x: (x[0], x[0] - x[1]))
        self.starts = [s for s, _, _ in self.spans]
        self.reach = list(itertools.accumulate((e for _, e, _ in self.spans), max))

    def at(self, t: float):
        k = bisect.bisect_right(self.starts, t) - 1
        while k >= 0 and self.reach[k] >= t:  # a span opened by k still runs at t
            _, e, name = self.spans[k]
            if e >= t:
                return name
            k -= 1
        return None


def is_kernel(op: list) -> bool:
    """A Pallas kernel launch (the sweep kernel is the only one the
    service runs)."""
    return op[3] == "tpu_custom_call"


def reduce(rec: dict) -> dict:
    """Busy union, kernel time, op totals and labelled idle gaps of a
    normalized record, and for each host span name its durations inside
    the window (``spans``) and the idle time under its intervals
    (``idle_under``).  An idle gap is labelled with the innermost program
    span open at its midpoint, else the innermost benchmark annotation,
    else ``bench.window``.  Times in seconds; per-device values averaged."""
    w0, w1 = rec["window"]
    window_s = (w1 - w0) / 1e9
    devs = rec["devices"]
    if not devs:
        raise RuntimeError("the trace holds no device operations")
    host = rec["host"]
    names = sorted({n for n, _, _ in host})
    program = _Innermost([h for h in host if not h[0].startswith(BENCH_PREFIX)])
    bench = _Innermost([h for h in host if h[0].startswith(BENCH_PREFIX)])
    covered = {name: _union([[max(s, w0), min(s + d, w1)] for n, s, d in host
                             if n == name and min(s + d, w1) > max(s, w0)])
               for name in names}
    busy = kernel = 0.0
    ops, gaps = {}, {}
    idle_under = dict.fromkeys(names, 0.0)
    longest = []
    for evs in devs.values():
        clipped = [[e[0], max(e[1], w0), min(e[1] + e[2], w1), e] for e in evs]
        clipped = [c for c in clipped if c[2] > c[1]]
        u = _union([[c[1], c[2]] for c in clipped])
        busy += sum(e - s for s, e in u) / 1e9
        for name, s, e, op in clipped:
            ops[name] = ops.get(name, 0.0) + (e - s) / 1e9
            if is_kernel(op):
                kernel += (e - s) / 1e9
        edges = [w0] + [x for iv in u for x in iv] + [w1]
        idle = [[s, e] for s, e in zip(edges[::2], edges[1::2]) if e > s]
        for s, e in idle:
            mid = (s + e) / 2
            label = program.at(mid) or bench.at(mid) or WINDOW
            gaps[label] = gaps.get(label, 0.0) + (e - s) / 1e9
            longest.append((e - s) / 1e9)
        for name in names:
            idle_under[name] += _overlap(idle, covered[name]) / 1e9
    nd = len(devs)
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return dict(
        window_s=window_s,
        busy_s=busy / nd,
        kernel_s=kernel,  # summed over devices
        devices=nd,
        device_ops=[[k, v / nd] for k, v in top_ops],
        idle_gaps=[[k, v / nd] for k, v in top_gaps],
        longest_gap_s=max(longest, default=0.0),
        spans={name: [d / 1e9 for n, s, d in host if n == name and w0 <= s and s + d <= w1]
               for name in names},
        idle_under={name: v / nd for name, v in idle_under.items()},
        counters=rec.get("counters", {}),
    )


def save(rec: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(rec, f)


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)
