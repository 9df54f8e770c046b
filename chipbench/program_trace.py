"""Reductions of what the program records about itself in a trace.

The program marks its own work (``README.md``, "The streaming serving
stack"): host spans named ``repro.*`` (``repro.flush`` once per server
flush, ``repro.query`` once per ``QueryEngine.query`` call,
``repro.dispatch`` once per launched chunk of a plan that runs the query
tower, ...), and named device scopes (``tower``, ``route``, ``scan``,
``merge``) in the ``op_name`` metadata of every plan's ops.

The span reductions read a :class:`trace.Trace` as ``trace.load`` keeps
it. The scope reductions also need what ``trace.load`` drops: which
compiled module each device op ran in (the device plane's module line,
:func:`load_modules`) and each module's instruction → scope map, taken
from the compiled text of the plans the window ran
(:func:`instruction_scopes`). Every function returns None, or nothing,
on a trace without the program's spans, as a program older than them
writes.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re


SCOPES = ("tower", "route", "scan", "merge")
# the kernel wrapper's lane-major copies of the side buffers: part of
# the scan's preparation, under a scope of their own inside ``scan``
RELAYOUT = "relayout"
MODULE_LINE = "XLA Modules"


# ---------------------------------------------------------------------------
# Host spans
# ---------------------------------------------------------------------------


def spans(trace, name, lo, hi):
    """``(start, end)`` of the host spans ``name`` lying in ``[lo, hi]``."""
    return [(s, s + d) for _, s, d in trace.spans(name)
            if s >= lo and s + d <= hi]


def merged(ops):
    """Union of ``(name, start, dur)`` device ops as sorted, disjoint
    ``(start, end)`` intervals."""
    out = []
    for _, s, d in sorted(ops, key=lambda e: e[1]):
        e = s + d
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def covered_ns(intervals, starts, a, b):
    """Length of ``[a, b]`` covered by disjoint sorted ``intervals``
    (``starts`` their start times)."""
    total = 0
    i = max(bisect.bisect_right(starts, a) - 1, 0)
    while i < len(intervals) and intervals[i][0] < b:
        s, e = intervals[i]
        total += max(0, min(e, b) - max(s, a))
        i += 1
    return total


def span_idle_ms(trace, window, name):
    """Mean time (ms) per host span ``name`` in the window with no device
    op running, averaged over the devices; None without such spans."""
    lo, hi = window
    found = spans(trace, name, lo, hi)
    if not found or not trace.devices:
        return None
    idle = 0.0
    for dev in trace.devices:
        iv = merged(dev)
        starts = [s for s, _ in iv]
        idle += sum((b - a) - covered_ns(iv, starts, a, b) for a, b in found)
    return idle / len(trace.devices) / len(found) / 1e6


def spans_within(trace, window, inner, outer):
    """Mean count of host spans ``inner`` inside each span ``outer`` in
    the window; None without ``outer`` spans."""
    lo, hi = window
    outs = spans(trace, outer, lo, hi)
    if not outs:
        return None
    ins = spans(trace, inner, lo, hi)
    starts = [s for s, _ in ins]
    n = 0
    for a, b in outs:
        i = bisect.bisect_left(starts, a)
        while i < len(ins) and ins[i][0] <= b:
            n += ins[i][1] <= b
            i += 1
    return n / len(outs)


# ---------------------------------------------------------------------------
# Device scopes
# ---------------------------------------------------------------------------

_LINE = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*)$", re.M)
_OPERAND = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'metadata=\{op_name="([^"]*)"')


def scope_of(op_name):
    """The innermost of :data:`SCOPES` and :data:`RELAYOUT` on an
    ``op_name`` path, or None."""
    return next((p for p in reversed(op_name.split("/"))
                 if p in SCOPES or p == RELAYOUT), None)


def instruction_scopes(hlo_text):
    """{instruction name: scope or None} of a compiled module's text.

    An instruction whose metadata names a path from the plan's
    ``jit(...)`` takes the innermost scope on that path (None outside
    every scope). One without such metadata, as XLA makes when it
    hoists a weight's convert out of a loop or starts an async copy of a
    parameter, takes the scope most of its users have: the work is done
    for them."""
    own, users, order = {}, {}, []
    for m in _LINE.finditer(hlo_text):
        name, rest = m[1], m[2]
        order.append(name)
        op = _OP_NAME.search(rest)
        if op and op[1].startswith("jit("):
            own[name] = scope_of(op[1])
        for used in _OPERAND.findall(rest.split(", metadata=")[0]):
            users.setdefault(used, []).append(name)
    out = {}
    for name in reversed(order):    # a computation lists users after operands
        if name in own:
            out[name] = own[name]
            continue
        votes = collections.Counter(
            out[u] for u in users.get(name, ()) if out.get(u))
        out[name] = votes.most_common(1)[0][0] if votes else None
    return out


def module_name(text):
    """``jit_query_fn(1234)`` → ``jit_query_fn``; the compiled module's
    ``HloModule`` name."""
    return text.split("(", 1)[0].strip()


def load_modules(profile_dir):
    """Per TPU, as ``trace.load`` orders them, the executions on the
    device plane's module line: ``[(module name, start_ns, dur_ns), ...]``
    (empty where the plane has no such line)."""
    from jax.profiler import ProfileData

    from chipbench import trace as trace_lib
    paths = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    out = []
    for plane in pd.planes:
        if not plane.name.startswith(trace_lib.DEVICE_PREFIX):
            continue
        mods = [(module_name(ev.name), int(ev.start_ns), int(ev.duration_ns))
                for line in plane.lines if line.name == MODULE_LINE
                for ev in line.events]
        out.append(sorted(mods, key=lambda e: e[1]))
    return out


def op_scopes(trace, modules, maps):
    """Per device, each device op as ``(category, start_ns, dur_ns)``:
    ``"kernel"`` for a Mosaic kernel, else its instruction's scope in
    the module execution it ran in (None where the op ran outside any
    module with a map, or its instruction has no scope). ``maps`` is
    {module name: [instruction → scope map, ...]}, one map per compiled
    plan of that name; an execution takes the map that names most of
    its ops."""
    kernels = set(trace.kernel_names)
    out = []
    for dev, mods in zip(trace.devices, modules):
        starts = [s for _, s, _ in dev]
        cats = [None] * len(dev)
        for name, s, d in mods:
            lo = bisect.bisect_left(starts, s)
            hi = bisect.bisect_right(starts, s + d)
            ran = [dev[i][0] for i in range(lo, hi)]
            best = max(maps.get(name, ()), default={},
                       key=lambda m: sum(n in m for n in ran))
            for i in range(lo, hi):
                cats[i] = best.get(dev[i][0])
        out.append([("kernel" if n in kernels else c, s, d)
                    for c, (n, s, d) in zip(cats, dev)])
    return out


def exclusive_ns(ops, lo, hi):
    """{category: device ns in ``[lo, hi]``} where each instant belongs to
    the innermost op running then (the latest started: a loop's body
    ops own their time, the loop op what lies between them). ``ops``:
    ``(category, start_ns, dur_ns)`` sorted by start, the longer first
    on a tie. The values add up to the union of the ops' intervals."""
    out = {}
    stack = []           # (end, category) of the ops open at t, innermost last
    t = lo

    def run_to(to):
        nonlocal t
        while t < to:
            while stack and stack[-1][0] <= t:
                stack.pop()
            if not stack:
                t = to
                return
            end, cat = stack[-1]
            until = min(end, to)
            out[cat] = out.get(cat, 0) + until - t
            t = until

    for cat, s, d in ops:
        s, e = max(s, lo), min(s + d, hi)
        if e <= s:
            continue
        run_to(s)
        stack.append((e, cat))
    run_to(hi)
    return out


def step_scope_ns(scoped, windows):
    """Per ``(start, end)`` window (a step), {category: device ns},
    averaged over the devices; ``scoped`` is :func:`op_scopes`'s."""
    out = [dict() for _ in windows]
    for dev in scoped:
        dev = sorted(dev, key=lambda e: (e[1], -e[2]))
        starts = [s for _, s, _ in dev]
        longest = max((d for _, _, d in dev), default=0)
        for acc, (a, b) in zip(out, windows):
            i = bisect.bisect_left(starts, a - longest)
            j = bisect.bisect_left(starts, b)
            for cat, ns in exclusive_ns(dev[i:j], a, b).items():
                acc[cat] = acc.get(cat, 0) + ns / len(scoped)
    return out
