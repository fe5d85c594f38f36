"""A profiler trace reduced by what the serving program names: device
time by ``jax.named_scope`` (``SCOPES``, set in ``models/model.py``,
``models/steps.py`` and the page writes of ``models/attention.py``), and
idle gaps labelled by the program's own host spans (``repro.obs``
``orch.*`` and ``batcher.*`` annotations) inside the benchmark's
``bench.*`` wrappers.

``reduce_file`` returns ``trace_reduce``'s reduction over the same
events, with two changes: each idle gap takes the innermost span open at
its midpoint, program spans included; and ``breakdown["device_scopes"]``
lists device seconds by ``<program>/<scope>``, each operation charged to
the innermost scope of ``SCOPES`` on its name stack (``page_write`` sits
inside ``attn``) and only for the time no operation nested in it on the
same line runs, so that a loop is not counted with its body. An
operation without a scope is charged to ``<program>/-``.

A TPU trace's operation events carry no name stack: an event's name is
its HLO instruction and its stats are offsets. The stack is the
instruction's ``op_name`` in the HLO the profiler stores for each program
it saw (``Hlo Proto`` stats of the ``/host:metadata`` plane, which
``ProfileData`` does not expose), read here from the file's protobuf wire
format.
"""
from __future__ import annotations

from bisect import bisect_right

import trace_reduce

SCOPES = ("embed", "attn", "page_write", "mlp", "lm_head", "emit")
PROGRAM_SPANS = ("orch.", "batcher.")
ANCHOR = "obs.clock_anchor"


def scope_of(stack: str | None) -> str | None:
    """The innermost ``SCOPES`` entry of a ``/``-separated name stack."""
    hit = None
    for part in (stack or "").split("/"):
        if part in SCOPES:
            hit = part
    return hit


# ------------------------------------------------- protobuf wire format

def _varint(b, i):
    x = s = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << s
        if c < 0x80:
            return x, i
        s += 7


def _fields(b):
    """(field number, value) of one message's fields, in order: ints for
    varints, memoryview slices for everything else."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wt = key & 7
        if wt == 0:
            v, i = _varint(b, i)
        elif wt == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wt in (1, 5):
            ln = 8 if wt == 1 else 4
            v, i = b[i:i + ln], i + ln
        else:
            raise ValueError(f"protobuf wire type {wt}")
        yield key >> 3, v


def _text(v) -> str:
    return bytes(v).decode()


def _op_names(hlo_proto) -> dict:
    """{instruction: op_name} of a serialized ``HloProto`` (hlo_module 1;
    module computations 3; computation instructions 2; instruction name
    1, metadata 7; metadata op_name 2)."""
    out = {}
    for f, module in _fields(hlo_proto):
        if f != 1:
            continue
        for g, comp in _fields(module):
            if g != 3:
                continue
            for h, ins in _fields(comp):
                if h != 2:
                    continue
                name = meta = None
                for k, v in _fields(ins):
                    if k == 1:
                        name = _text(v)
                    elif k == 7:
                        meta = v
                if name and meta is not None:
                    op = dict(_fields(meta)).get(2)
                    if op:
                        out[name] = _text(op)
    return out


def name_stacks(path) -> dict:
    """{program event name, e.g. ``jit_fused_decode_step(123)``:
    {instruction: op_name}} from the ``Hlo Proto`` stats of the trace's
    ``/host:metadata`` plane (XSpace planes 1; plane name 2, event
    metadata map 4, stat metadata map 5; event metadata name 2, stats 5;
    stat metadata id 1, bytes value 6)."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    out = {}
    for f, plane in _fields(data):
        if f != 1:
            continue
        fields = list(_fields(plane))
        if _text(next((v for k, v in fields if k == 2), b"")) \
                != "/host:metadata":
            continue
        stat_names = {}
        for k, v in fields:
            if k == 5:
                entry = dict(_fields(v))
                stat_names[entry[1]] = _text(dict(_fields(entry[2]))
                                             .get(2, b""))
        for k, v in fields:
            if k != 4:
                continue
            meta = list(_fields(dict(_fields(v))[2]))
            name = _text(next((x for g, x in meta if g == 2), b""))
            for g, stat in meta:
                st = dict(_fields(stat)) if g == 5 else {}
                if stat_names.get(st.get(1)) == "Hlo Proto" and 6 in st:
                    out[name] = _op_names(st[6])
    return out


# ------------------------------------------------------------ reduction

def events(pd):
    """(device events by line name, operations by device, host spans, the
    clock anchor's start or None): device events are (name, start_ns,
    end_ns) as ``trace_reduce.events`` gives them; a device's operations
    are its ``XLA Modules`` and ``XLA Ops`` events; host spans are the
    benchmark's and the program's annotations."""
    dev, ops, host, anchor = {}, {}, [], None
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            mods, own = [], []
            ops[plane.name] = (mods, own)
            for line in plane.lines:
                evs = dev.setdefault(line.name, [])
                for ev in line.events:
                    e = (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    evs.append(e)
                    if line.name == "XLA Modules":
                        mods.append(e)
                    elif line.name == "XLA Ops":
                        own.append(e)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == ANCHOR:
                        anchor = ev.start_ns
                    elif ev.name in trace_reduce.HOST_SPANS \
                            or ev.name.startswith(PROGRAM_SPANS):
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
    return dev, ops, host, anchor


def exclusive(ops, window):
    """[(key, seconds)] for each operation (key, start_ns, end_ns) on one
    line, clipped to the window, less the time operations nested inside
    it take."""
    w0, w1 = window
    evs = sorted(((k, max(s, w0), min(e, w1)) for k, s, e in ops
                  if e > w0 and s < w1), key=lambda o: (o[1], -o[2]))
    own = [e - s for _k, s, e in evs]
    stack = []                         # indices of open operations
    for i, (_k, s, e) in enumerate(evs):
        while stack and evs[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(e, evs[stack[-1]][2]) - s
        stack.append(i)
    return [(k, t * 1e-9) for (k, _s, _e), t in zip(evs, own)]


def device_scopes(ops, stacks, window=None):
    """[["<program>/<scope>", seconds]] by device time summed over the
    devices of ``ops`` (as ``events`` gives them), most first: each
    operation under the ``XLA Modules`` event its start falls in, its
    scope from that program's name stacks (``name_stacks``)."""
    if window is None:
        allev = [ev for mods, own in ops.values() for ev in mods + own]
        if not allev:
            return []
        window = (min(s for _, s, _ in allev), max(e for _, _, e in allev))
    acc = {}
    for mods, own in ops.values():
        mods = sorted(mods, key=lambda m: m[1])
        starts = [s for _n, s, _e in mods]
        tagged = []
        for name, s, e in own:
            i = bisect_right(starts, s) - 1
            if i >= 0 and s < mods[i][2]:
                prog = trace_reduce.module_name(mods[i][0])
                op = stacks.get(mods[i][0], {}).get(
                    trace_reduce.op_name(name))
            else:
                prog, op = "-", None
            tagged.append((f"{prog}/{scope_of(op) or '-'}", s, e))
        for key, t in exclusive(tagged, window):
            acc[key] = acc.get(key, 0.0) + t
    return [[k, t] for k, t in sorted(acc.items(), key=lambda kv: -kv[1])]


def reduce(dev, ops, host, stacks, window=None, top=10):
    r = trace_reduce.reduce(dev, host, window, top)
    if r is not None:
        r["breakdown"]["device_scopes"] = device_scopes(ops, stacks, window)
    return r


def reduce_file(path, window=None, top=10):
    """(the reduction, the ``obs.clock_anchor`` event's start or None,
    the host spans)."""
    from jax.profiler import ProfileData
    dev, ops, host, anchor = events(ProfileData.from_file(path))
    return (reduce(dev, ops, host, name_stacks(path), window, top), anchor,
            host)
