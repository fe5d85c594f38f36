"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: the device's busy time over the traced window, the device
time of each jitted program and of each named kernel, and the breakdown
(the operations that took the most time, the longest idle gaps labelled
by the benchmark's host span that was open meanwhile).

Only ``jax.profiler.ProfileData`` is used to read the file.
"""
from __future__ import annotations

import glob
import os
import re

KERNELS = ("paged_decode_attention", "chunked_prefill_attention")
HOST_SPANS = ("bench.orch_tick", "bench.batcher_tick")
_MODULE = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")


def newest_xplane(trace_dir) -> str | None:
    files = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def module_name(name: str) -> str:
    """``jit_fused_decode_step(123)`` -> ``fused_decode_step``."""
    return _MODULE.match(name).group(1)


def op_name(name: str) -> str:
    """An XLA op event's name up to its HLO text: ``%while.5 = (...)``
    -> ``while.5``."""
    return name.split(" = ")[0].lstrip("%")


def _union(intervals):
    """Total length and the sorted disjoint intervals of a union."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return sum(e - s for s, e in out), out


def events(pd):
    """(device events by line name, host span events): each event is
    (name, start_ns, end_ns). Devices are the ``/device:TPU:<n>`` planes
    (``/device:CPU`` style planes of other backends are taken too)."""
    dev, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                evs = dev.setdefault(line.name, [])
                for ev in line.events:
                    evs.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
    return dev, host


def reduce(dev, host, window=None, top=10):
    """``dev``/``host`` as from ``events``; ``window`` (start_ns, end_ns)
    limits everything to the traced window (default: the span of the
    device's events). Returns the reduction as a dict."""
    mods = dev.get("XLA Modules", [])
    ops = dev.get("XLA Ops", [])
    if window is None:
        allev = mods + ops
        if not allev:
            return None
        window = (min(s for _, s, _ in allev), max(e for _, _, e in allev))
    w0, w1 = window

    def inside(evs):
        return [(n, max(s, w0), min(e, w1)) for n, s, e in evs
                if e > w0 and s < w1]

    mods, ops = inside(mods), inside(ops)
    busy_src = mods if mods else ops
    busy_ns, spans = _union([(s, e) for _, s, e in busy_src])
    modules = {}
    for n, s, e in mods:
        modules.setdefault(module_name(n), []).append((e - s) * 1e-9)
    kernels = {}
    op_time = {}
    for n, s, e in ops:
        short = op_name(n)
        op_time[short] = op_time.get(short, 0.0) + (e - s) * 1e-9
        for k in KERNELS:
            if k in n:
                kernels[k] = kernels.get(k, 0.0) + (e - s) * 1e-9
    gaps = []
    prev = w0
    for s, e in spans:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if w1 > prev:
        gaps.append((prev, w1))
    gaps.sort(key=lambda g: g[0] - g[1])
    host = sorted(host, key=lambda h: h[1])

    def label(g0, g1):
        mid = (g0 + g1) / 2
        open_ = [n for n, s, e in host if s <= mid < e]
        return open_[-1] if open_ else "no benchmark span (waiting)"

    idle = {}
    for g0, g1 in gaps:
        k = label(g0, g1)
        idle[k] = idle.get(k, 0.0) + (g1 - g0) * 1e-9
    breakdown = {
        "device_ops": [[n, t] for n, t in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, t] for n, t in sorted(
            idle.items(), key=lambda kv: -kv[1])[:top]],
    }
    return {"busy_s": busy_ns * 1e-9, "window_s": (w1 - w0) * 1e-9,
            "modules": modules, "kernels": kernels,
            "decode_steps": len(modules.get("fused_decode_step", [])),
            "breakdown": breakdown}


def reduce_file(path, window=None, top=10):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    dev, host = events(pd)
    return reduce(dev, host, window, top)
