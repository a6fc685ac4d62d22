"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

On a TPU the profiler writes one plane per chip, ``/device:TPU:<i>``,
whose line ``XLA Ops`` holds one event per operation that ran, named by
its HLO text (``%name = shape opcode(operands), ...``); a Pallas kernel
is a ``custom-call`` with ``custom_call_target="tpu_custom_call"``.  The
host's plane ``/host:CPU`` holds the harness's own ``TraceAnnotation``
spans on the same clock.  Times are in nanoseconds.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPANS = ("window", "dispatch", "sync")
# control flow: such an op's event spans the ops of its body, which have
# events of their own, so it is left out (a scan over rounds is one
# ``while`` as long as the whole call)
CONTAINERS = ("while", "conditional", "call")

_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")


@dataclass
class Op:
    name: str            # the HLO text
    start: float         # ns
    dur: float           # ns

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class Trace:
    devices: dict = field(default_factory=dict)   # plane name -> [Op]
    spans: list = field(default_factory=list)     # (name, start, end) host

    def window(self):
        """(start, end) of the harness's ``window`` span, else of all
        device ops."""
        w = [(s, e) for n, s, e in self.spans if n == "window"]
        if w:
            return min(s for s, _ in w), max(e for _, e in w)
        ops = [o for v in self.devices.values() for o in v]
        return min(o.start for o in ops), max(o.end for o in ops)


def load(path) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    t = Trace()
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [Op(e.name, e.start_ns, e.duration_ns)
                            for e in line.events
                            if opcode(e.name) not in CONTAINERS]
            t.devices[plane.name] = ops
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name in SPANS:
                        t.spans.append((e.name, e.start_ns,
                                        e.start_ns + e.duration_ns))
    return t


def merged(ops, lo: float, hi: float):
    """The union of the ops' intervals clipped to [lo, hi], as sorted
    disjoint (start, end) pairs."""
    iv = sorted((max(o.start, lo), min(o.end, hi)) for o in ops
                if o.end > lo and o.start < hi)
    out = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def busy_ns(ops, lo: float, hi: float) -> float:
    return sum(e - s for s, e in merged(ops, lo, hi))


def gaps(ops, lo: float, hi: float):
    """Idle (start, end) intervals of the device inside [lo, hi]."""
    out, t = [], lo
    for s, e in merged(ops, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label(spans, t: float) -> str:
    """What the host was doing at ``t``: the shortest harness span that
    holds it, or ``host`` outside all but the window."""
    best = None
    for name, s, e in spans:
        if name != "window" and s <= t <= e and (
                best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "host"


def opcode(text: str) -> str:
    """The HLO opcode of an op's text (``fusion``, ``custom-call``)."""
    m = re.search(r"\s([\w-]+)\(", text.partition(" = ")[2])
    return m.group(1) if m else ""


def op_name(text: str) -> str:
    """``%fusion.3 = f32[23,1]{...} fusion(...)`` -> ``fusion.3 f32[23,1]
    fusion``: the op, its output shape and its opcode."""
    head, _, rest = text.partition(" = ")
    shape = _SHAPE.match(rest)
    return " ".join(x for x in (head.lstrip("%"),
                                shape.group(0) if shape else "",
                                opcode(text)) if x)


def custom_call(text: str):
    """(output shape, [operand shapes]) of a Pallas kernel op, as
    (dtype, dims) pairs; None for any other op."""
    if 'custom_call_target="tpu_custom_call"' not in text:
        return None
    _, _, rest = text.partition(" = ")
    head, _, args = rest.partition("custom-call(")
    out = _SHAPE.search(head)
    operands = _SHAPE.findall(args.split("), ")[0])

    def dims(s):
        return tuple(int(d) for d in s.split(",") if d)
    return ((out.group(1), dims(out.group(2))),
            [(dt, dims(d)) for dt, d in operands])


def top_ops(ops, lo: float, hi: float, k: int = 10):
    """The k ops (by short name) that took the most device time."""
    tot = {}
    for o in ops:
        if o.end > lo and o.start < hi:
            n = op_name(o.name)
            tot[n] = tot.get(n, 0.0) + min(o.end, hi) - max(o.start, lo)
    return sorted(tot.items(), key=lambda kv: -kv[1])[:k]
