"""From a profiler trace to the numbers the per-layer metrics read.

The reduction works on plain event lists, so that it can be checked on a
constructed trace: `DeviceEvent`s (the operations that ran on a chip) and
`HostEvent`s (spans of the host's threads, among them the benchmark's own
`jax.profiler.TraceAnnotation`s, all named `bench_*`). `read_xplane` turns
the `.xplane.pb` file that `jax.profiler` writes into those lists.

- busy: the union of the device intervals inside the window, averaged
  over the chips; idle share = 1 - busy / window.
- kernel time: the summed device durations of the events whose name
  holds the kernel's name (the program gives each Pallas kernel a stable
  function name, which the TPU's trace carries).
- breakdown: the ten operations that took most device time of their own
  (net of the operations nested in them), and the ten longest idle gaps,
  each named by the host span that covered most of it.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import NamedTuple


class DeviceEvent(NamedTuple):
    device: int
    name: str
    start_ns: float
    dur_ns: float


class HostEvent(NamedTuple):
    thread: str
    name: str
    start_ns: float
    dur_ns: float


WINDOW_SPAN = "bench_window"


def op_name(hlo: str) -> str:
    """The short name of a TPU trace event, whose name is the HLO text of
    the operation ("%cholupdate.1 = f32[...] custom-call(...), ..."): the
    instruction name, with the custom-call target where there is one.
    Operands are dropped, so an op that merely reads a kernel's output is
    never taken for the kernel."""
    name = hlo.split(" = ", 1)[0].lstrip("%")
    target = hlo.split('custom_call_target="', 1)
    if len(target) == 2:
        name += " (" + target[1].split('"', 1)[0] + ")"
    return name


def read_xplane(trace_dir: str):
    """(device events, host events) of the newest trace under trace_dir.

    Device events come from the "XLA Ops" line of each "/device:TPU:<n>"
    plane (the operations the chip ran); host events from every line of
    the "/host:CPU" plane."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    dev, host = [], []
    for plane in pd.planes:
        name = plane.name
        if name.startswith("/device:TPU:") and name[12:].isdigit():
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    dev.append(DeviceEvent(int(name[12:]), op_name(e.name),
                                           float(e.start_ns),
                                           float(e.duration_ns)))
        elif name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0:
                        host.append(HostEvent(line.name, e.name,
                                              float(e.start_ns),
                                              float(e.duration_ns)))
    return dev, host


def window_of(host):
    """(start_ns, end_ns) of the benchmark's window span."""
    spans = [h for h in host if h.name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    w = max(spans, key=lambda h: h.dur_ns)
    return w.start_ns, w.start_ns + w.dur_ns


def _clip(events, w0, w1):
    out = []
    for e in events:
        a, b = max(e.start_ns, w0), min(e.start_ns + e.dur_ns, w1)
        if b > a:
            out.append((a, b, e))
    return out


def union(intervals):
    """Merge (start, end) intervals -> sorted disjoint list."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


class Reduced(NamedTuple):
    window_s: float
    busy_s: float              # union of device intervals, mean over chips
    devices: int
    device_ops: list           # [(name, self seconds)] largest first
    idle_gaps: list            # [(host span, seconds)] longest first
    kernel_ns: dict            # op name -> summed device ns in the window
    kernel_count: dict         # op name -> events in the window

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_seconds(self, pattern: str):
        """(seconds, events) of the operations whose name holds
        `pattern`; (0.0, 0) when none ran."""
        ns = sum(v for k, v in self.kernel_ns.items() if pattern in k)
        n = sum(v for k, v in self.kernel_count.items() if pattern in k)
        return ns * 1e-9, n


def reduce(dev, host, top: int = 10) -> Reduced:
    w0, w1 = window_of(host)
    clipped = _clip(dev, w0, w1)
    by_dev = defaultdict(list)
    kernel_ns, kernel_count = defaultdict(float), defaultdict(int)
    for a, b, e in clipped:
        by_dev[e.device].append((a, b))
        kernel_ns[e.name] += b - a
        kernel_count[e.name] += 1
    self_ns = _self_times(clipped)
    devices = max(1, len(by_dev))
    busy_ns, gaps = 0.0, []
    for d, iv in by_dev.items():
        merged = union(iv)
        busy_ns += sum(b - a for a, b in merged)
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    if not by_dev:
        gaps = [(w0, w1)]
    gaps.sort(key=lambda g: g[0] - g[1])
    ops = sorted(self_ns.items(), key=lambda kv: -kv[1])[:top]
    return Reduced(
        window_s=(w1 - w0) * 1e-9, busy_s=busy_ns / devices * 1e-9,
        devices=devices,
        device_ops=[[name, ns * 1e-9] for name, ns in ops],
        idle_gaps=[[_host_label(host, a, b), (b - a) * 1e-9]
                   for a, b in gaps[:top]],
        kernel_ns=dict(kernel_ns), kernel_count=dict(kernel_count))


def _self_times(clipped):
    """Device time of each op name net of the ops nested inside it (a
    `while` loop's event spans every op of its body)."""
    self_ns = defaultdict(float)
    for dev in {e.device for _, _, e in clipped}:
        stack = []                       # [(end, name)] open parents
        for a, b, e in sorted((c for c in clipped if c[2].device == dev),
                              key=lambda c: (c[0], -c[1])):
            while stack and stack[-1][0] <= a:
                stack.pop()
            if stack and b <= stack[-1][0]:
                self_ns[stack[-1][1]] -= b - a
            self_ns[e.name] += b - a
            stack.append((b, e.name))
    return dict(self_ns)


def _host_label(host, a, b) -> str:
    """The host span that covered most of [a, b): the benchmark's own
    spans first, then any other host span; "untraced host" if none."""
    best, best_ov = None, 0.0
    for own in (True, False):
        for h in host:
            if h.name == WINDOW_SPAN or h.name.startswith("bench_") != own:
                continue
            ov = min(b, h.start_ns + h.dur_ns) - max(a, h.start_ns)
            if ov > best_ov:
                best, best_ov = h.name, ov
        if best is not None:
            return best[:80]
    return "untraced host"


def idle_percent(ctx):
    """Per-layer reader of the device's idle share of the window, in %;
    None when no operation ran on the device."""
    if ctx.trace.busy_s <= 0:
        return None
    return 100.0 * ctx.trace.idle_share
