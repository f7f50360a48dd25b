"""The profiled stretch of a traced run, and its reduction to numbers.

`Window` opens a `jax.profiler` trace around a stretch of the measured
window, marks it with a host annotation (``bench.window``), and turns the
recorded trace into a small normalized record:

    {"window": [start_ns, end_ns],
     "devices": {plane: [[label, start_ns, dur_ns, opcode], ...]},
     "host": [[name, start_ns, dur_ns], ...],
     "counters": {...}}

``host`` holds the benchmark's own annotations and the program's
telemetry spans placed on the profiler's clock.  `reduce` reads only
this record, so the committed sample trace (``bench/trace_sample/``)
checks the same arithmetic the runs use.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import shutil
import tempfile
import time

import jax

HOST_SPANS = ("sched.admit", "sched.step", "engine.launch")
# Where the host was, innermost first, for an idle gap at a given instant.
GAP_LABELS = (
    ("sched.admit", "scheduler: admission"),
    ("engine.launch", "engine: launch dispatch to ready"),
    ("sched.step", "scheduler: job hooks and retirement"),
    ("bench.submit", "client: submit"),
    ("bench.wait", "client: waiting for arrivals"),
    ("bench.step", "client: step call"),
)


class Window:
    def __init__(self, trace_dir, server):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_", dir=trace_dir)
        self.server = server
        self.active = False
        self.record = None

    def start(self) -> None:
        srv = self.server
        jax.profiler.start_trace(self.dir)
        self.ann = jax.profiler.TraceAnnotation("bench.window")
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        self.tel0 = srv.telemetry.now_us()
        self.c0 = (srv.busy_slot_sweeps, srv.total_slot_sweeps, srv.launches)
        self.active = True

    def stop(self) -> None:
        srv = self.server
        jax.block_until_ready(srv.carry)
        self.c1 = (srv.busy_slot_sweeps, srv.total_slot_sweeps, srv.launches)
        self.tel1 = srv.telemetry.now_us()
        self.ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False
        events = srv.telemetry.events()
        try:
            self.record = normalize(self.dir, events, self.tel0, self.tel1)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        self.record["counters"] = dict(
            busy_slot_sweeps=self.c1[0] - self.c0[0],
            total_slot_sweeps=self.c1[1] - self.c0[1],
            launches=self.c1[2] - self.c0[2])

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


def normalize(logdir: str, tel_events: list, tel0_us: float, tel1_us: float) -> dict:
    """Read the newest xplane under ``logdir`` into the normalized record."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise RuntimeError(f"no profiler trace was written under {logdir}")
    pd = ProfileData.from_file(paths[-1])
    window, host, devices = None, [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" in lines:
                devices[plane.name] = _device_ops(lines)
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name == "bench.window":
                        window = [int(ev.start_ns), int(ev.start_ns + ev.duration_ns)]
                    elif ev.name.startswith("bench."):
                        host.append([ev.name, int(ev.start_ns), int(ev.duration_ns)])
    if window is None:
        raise RuntimeError("the trace holds no bench.window annotation")
    # The program's telemetry spans, moved onto the profiler's clock: the
    # window annotation opened at telemetry time tel0_us.
    scale = (window[1] - window[0]) / max(1e-9, (tel1_us - tel0_us) * 1e3)
    to_ns = lambda us: window[0] + (us - tel0_us) * 1e3 * min(1.0, scale)
    opened = {}
    for ev in tel_events:
        name, ph = ev.get("name"), ev.get("ph")
        if name not in HOST_SPANS:
            continue
        if ph == "B":
            opened[name] = ev["ts"]
        elif ph == "E" and name in opened:
            t0 = opened.pop(name)
            if t0 >= tel0_us and ev["ts"] <= tel1_us:
                host.append([name, int(to_ns(t0)), int((ev["ts"] - t0) * 1e3)])
        elif ph == "X" and ev["ts"] >= tel0_us and ev["ts"] + ev["dur"] <= tel1_us:
            host.append([name, int(to_ns(ev["ts"])), int(ev["dur"] * 1e3)])
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


def is_kernel(op: list) -> bool:
    """A Pallas kernel launch (the sweep kernel is the only one the
    service runs)."""
    return op[3] == "tpu_custom_call"


def _host_label(host: list, t: float) -> str:
    covering = {name for name, s, d in host if s <= t <= s + d}
    for key, label in GAP_LABELS:
        if key in covering:
            return label
    return "client: between steps"


def reduce(rec: dict) -> dict:
    """Busy union, kernel time, op totals and labelled idle gaps of a
    normalized record.  Times in seconds; per-device values averaged."""
    w0, w1 = rec["window"]
    window_s = (w1 - w0) / 1e9
    devs = rec["devices"]
    if not devs:
        raise RuntimeError("the trace holds no device operations")
    busy = kernel = 0.0
    ops, gaps = {}, {}
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
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                label = _host_label(rec["host"], (s + e) / 2)
                gaps[label] = gaps.get(label, 0.0) + (e - s) / 1e9
                longest.append((e - s) / 1e9)
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
        spans={name: [d / 1e9 for n, s, d in rec["host"] if n == name and w0 <= s and s + d <= w1]
               for name in HOST_SPANS},
        counters=rec.get("counters", {}),
    )


def save(rec: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(rec, f)


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)
