"""Reduction of a profiler trace to device busy time, kernel time and
idle gaps.

``load`` reads the newest ``.xplane.pb`` under a profile directory with
``jax.profiler.ProfileData`` and keeps, as plain lists of
``(name, start_ns, duration_ns)``:

* the device operations of each TPU (the ``XLA Ops`` line of each
  ``/device:TPU:<n>`` plane), and
* the host's events, among them the benchmark's own spans
  (``chipbench.*``, written with ``jax.profiler.TraceAnnotation``).

The reductions below work on those lists alone, so a recorded trace can
be kept as a small JSON fixture and reduced again by a test.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "chipbench."
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'


@dataclasses.dataclass
class Trace:
    devices: list          # per device: [(name, start_ns, dur_ns), ...]
    host: list             # [(name, start_ns, dur_ns), ...] every host event
    kernel_names: list     # device op names that are Mosaic kernels
    lines: list = dataclasses.field(default_factory=list)   # (plane, line, n)
    op_stats: dict = dataclasses.field(default_factory=dict)  # name → text

    def spans(self, name):
        """The benchmark's host spans called ``name``."""
        return [e for e in self.host if e[0] == name]

    def to_json(self):
        return {"devices": self.devices, "host": self.host,
                "kernel_names": sorted(self.kernel_names)}

    @classmethod
    def from_json(cls, obj):
        return cls([[tuple(e) for e in d] for d in obj["devices"]],
                   [tuple(e) for e in obj["host"]], list(obj["kernel_names"]))


def _stat_text(event):
    out = []
    for st in event.stats:
        try:
            out.append(f"{st[0]}={st[1]}")
        except Exception:  # noqa: BLE001 - a stat that does not print
            continue
    return " ".join(out)


def load(profile_dir):
    """Read the newest trace under ``profile_dir`` into a :class:`Trace`."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices, host, kernels, lines, stats = [], [], set(), [], {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = []
            for line in plane.lines:
                events = list(line.events)
                lines.append((plane.name, line.name, len(events)))
                if line.name != OPS_LINE:
                    continue
                for ev in events:
                    name = op_name(ev.name)
                    ops.append((name, int(ev.start_ns), int(ev.duration_ns)))
                    if name not in stats:
                        text = ev.name + " " + _stat_text(ev)
                        stats[name] = text if is_kernel(text) else text[:300]
                        if is_kernel(text):
                            kernels.add(name)
            devices.append(sorted(ops, key=lambda e: e[1]))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                events = list(line.events)
                lines.append((plane.name, line.name, len(events)))
                for ev in events:
                    host.append((ev.name, int(ev.start_ns),
                                 int(ev.duration_ns)))
    host.sort(key=lambda e: e[1])
    return Trace(devices, host, sorted(kernels), lines, stats)


def is_kernel(text):
    return KERNEL_MARK in text


def op_name(text):
    """The HLO instruction name of a device event (``%fusion.3 = ...`` →
    ``fusion.3``)."""
    return text.split(" = ", 1)[0].lstrip("%")


# ---------------------------------------------------------------------------
# Reductions
# ---------------------------------------------------------------------------


def union_ns(intervals, lo=None, hi=None):
    """Length of the union of ``(start, duration)`` intervals, clipped to
    ``[lo, hi]`` when given."""
    total, end = 0, None
    for s, d in sorted(intervals):
        e = s + d
        if lo is not None:
            s = max(s, lo)
        if hi is not None:
            e = min(e, hi)
        if e <= s:
            continue
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def window_of(trace):
    """``[lo, hi]`` of the benchmark's ``chipbench.window`` span, or of
    the device operations when the span is missing."""
    spans = trace.spans(SPAN_PREFIX + "window")
    if spans:
        _, s, d = spans[0]
        return s, s + d
    ops = [e for dev in trace.devices for e in dev]
    if not ops:
        return 0, 0
    return min(e[1] for e in ops), max(e[1] + e[2] for e in ops)


def busy_ns(trace, lo, hi):
    """Union of device operation intervals in ``[lo, hi]``, averaged over
    the devices."""
    if not trace.devices:
        return 0.0
    return sum(union_ns([(e[1], e[2]) for e in dev], lo, hi)
               for dev in trace.devices) / len(trace.devices)


def kernel_ns(trace, lo, hi):
    """Summed device time of the Mosaic kernels in ``[lo, hi]``, averaged
    over the devices."""
    names = set(trace.kernel_names)
    if not trace.devices:
        return 0.0
    return sum(union_ns([(e[1], e[2]) for e in dev if e[0] in names], lo, hi)
               for dev in trace.devices) / len(trace.devices)


def top_ops(trace, lo, hi, n=10):
    """The ``n`` device operations (by name) that took the most time,
    ``[[name, seconds], ...]``, averaged over the devices."""
    tot = {}
    for dev in trace.devices:
        for name, s, d in dev:
            if s >= lo and s + d <= hi:
                tot[name] = tot.get(name, 0) + d
    k = max(len(trace.devices), 1)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k / 1e9] for name, ns in best]


def idle_gaps(trace, lo, hi, n=10):
    """The ``n`` longest gaps between device operations on device 0 in
    ``[lo, hi]``, each named by the innermost host event that covers its
    middle: ``[[what the host was doing, seconds], ...]``."""
    if not trace.devices:
        return []
    gaps, end = [], lo
    for _, s, d in trace.devices[0]:
        if s > end:
            gaps.append((end, min(s, hi)))
        end = max(end, s + d)
        if end >= hi:
            break
    if end < hi:
        gaps.append((end, hi))
    gaps = sorted((g for g in gaps if g[1] > g[0]),
                  key=lambda g: g[0] - g[1])[:n]
    return [[host_activity(trace, (a + b) // 2), (b - a) / 1e9]
            for a, b in gaps]


def host_activity(trace, t):
    """Name of the shortest host event that covers time ``t``."""
    best = None
    for name, s, d in trace.host:
        if s > t:
            break
        if s + d >= t and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "no host event"


def step_windows(trace):
    """``(start, end)`` of each ``chipbench.step`` span, in trace time."""
    return [(s, s + d) for _, s, d in trace.spans(SPAN_PREFIX + "step")]


def _merged(intervals):
    """The union of ``(start, duration)`` intervals as disjoint, sorted
    ``(starts, ends, lengths before each)``, for :func:`_covered_ns`."""
    starts, ends = [], []
    for s, d in sorted(intervals):
        e = s + d
        if e <= s:
            continue
        if ends and s <= ends[-1]:
            ends[-1] = max(ends[-1], e)
        else:
            starts.append(s)
            ends.append(e)
    before = [0]
    for s, e in zip(starts, ends):
        before.append(before[-1] + e - s)
    return starts, ends, before


def _covered_ns(merged, a, b):
    """Length of ``[a, b]`` that a :func:`_merged` union covers (what
    ``union_ns(intervals, a, b)`` gives), by bisection."""
    starts, ends, before = merged
    i = bisect.bisect_right(ends, a)        # first piece ending past a
    j = bisect.bisect_left(starts, b)       # pieces [i, j) start before b
    if i >= j or b <= a:
        return 0
    return (before[j] - before[i] - max(0, a - starts[i])
            - max(0, ends[j - 1] - b))


def step_device_ns(trace):
    """Per ``chipbench.step`` span: (device busy ns, kernel ns) inside it,
    averaged over the devices. The host spans and the device operations
    share the trace's clock, so a step owns the device work that runs
    while its call is in flight. Each device's operations are merged once,
    so the cost grows with steps plus operations, not their product."""
    names = set(trace.kernel_names)
    k = max(len(trace.devices), 1)
    merged = [(_merged([(e[1], e[2]) for e in dev]),
               _merged([(e[1], e[2]) for e in dev if e[0] in names]))
              for dev in trace.devices]
    out = []
    for a, b in step_windows(trace):
        busy = sum(_covered_ns(m, a, b) for m, _ in merged)
        kern = sum(_covered_ns(m, a, b) for _, m in merged)
        out.append((busy / k, kern / k))
    return out
