"""Split a device trace by the stages of the round.

The program wraps each stage of a round in a ``jax.named_scope`` (the
names are :data:`SCOPES`, the program's ``repro.fl.telemetry.SCOPES``).
XLA keeps each op's scope path in its ``op_name`` metadata, and the
profiler writes it as the ``tf_op`` stat of the op's event metadata in
the ``.xplane.pb``, e.g. ``jit(step)/jit(similarity_stats)/pallas_call:``.
``jax.profiler.ProfileData`` exposes event stats only, so this module
reads the XSpace protobuf's wire format itself, with the standard
library: planes, lines, events, event metadata and stat metadata.

Attribution: an op belongs to the innermost of :data:`SCOPES` that is a
component of its ``tf_op`` path, a transform wrapper such as
``vmap(client_sgd)`` or ``transpose(jvp(guide_sgd))`` read as the name
inside it; an op on none of them is unscoped (``None``).  A fusion
carries its root op's path, so it is charged to its root op's scope.
An op XLA made without metadata of its own is charged through the
program's HLO graph, which the trace carries (:func:`graph_stages`).

A per-layer metric reads :func:`ms_per_round`.  The harness hands a
metric the reduced trace (``bench.trace``), not the file; until it also
hands over the file's path (``ctx.trace_path``), :func:`for_context`
finds the run's file among the harness's trace directories by matching
its device ops to the reduced trace.

As a script it measures one cell with its stages named:

    python -m bench.scopes --workload <cell> --seed <n> --seconds <s> [--out DIR]
    python -m bench.scopes --trace <file.xplane.pb>

The first builds the cell with the flight recorder on (the set-up split:
``fl.federation``, ``fl.engine``, and each compile with its seconds and
whether the persistent cache served it), runs four windows of
``--seconds``, the second and fourth with the recorder on and the
profiler recording, and prints one JSON line: rounds/s of each window,
and from the last window's trace the per-stage device time, the
unscoped remainder and its top ops, the Pallas kernels and the stage
each sits in, every per-layer metric of the cell, and the idle gaps
labelled by the host span that holds them (``fl.prepare``/``fl.launch``
inside the harness's ``dispatch``).  The second prints the split of a
trace already recorded.
"""
from __future__ import annotations

import glob
import os
import re
import struct
import tempfile
from dataclasses import dataclass
from typing import Optional

from . import trace as tr

SCOPES = ("client_sgd", "attack", "flatten", "guide_sgd", "step4_filter",
          "step5_fold", "eval")
TRACE_DIRS = "bench-trace-*"     # bench.run's profile directories
_WRAPPED = re.compile(r"[\w.-]+\((.*)\)")


# ----------------------------------------------------------------------
# The attribution rule
# ----------------------------------------------------------------------

def scope_path(tf_op: str):
    """The components of a ``tf_op`` path, each transform wrapper
    unwrapped and the trailing ``:<type>`` dropped."""
    head, sep, tail = tf_op.rpartition(":")
    if sep and "/" not in tail:
        tf_op = head
    out = []
    for c in tf_op.split("/"):
        m = _WRAPPED.fullmatch(c)
        while m:
            c = m.group(1)
            m = _WRAPPED.fullmatch(c)
        out.append(c)
    return out


def scope_of(tf_op: str) -> Optional[str]:
    """The innermost stage on the op's path, or None."""
    for c in reversed(scope_path(tf_op)):
        if c in SCOPES:
            return c
    return None


# ----------------------------------------------------------------------
# XSpace wire format
# ----------------------------------------------------------------------

def _varint(buf, i: int):
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def fields(buf):
    """(field number, wire type, value, start, end) of each field of one
    message: an int for varint fields, a slice of ``buf`` otherwise."""
    i, n = 0, len(buf)
    while i < n:
        start = i
        key, i = _varint(buf, i)
        f, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            v = buf[i:i + ln]
            i += ln
        elif wt in (1, 5):
            w = 8 if wt == 1 else 4
            v = buf[i:i + w]
            i += w
        else:
            raise ValueError(f"unsupported wire type {wt}")
        yield f, wt, v, start, i


def text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _stat(buf):
    """(stat metadata id, value) of one XStat; a ``ref_value`` comes back
    as ("ref", id)."""
    mid, val = 0, None
    for f, _, v, _, _ in fields(buf):
        if f == 1:
            mid = v
        elif f == 2:
            val = struct.unpack("<d", bytes(v))[0]
        elif f in (3, 4):
            val = v
        elif f == 5:
            val = text(v)
        elif f == 6:
            val = bytes(v)
        elif f == 7:
            val = ("ref", v)
    return mid, val


@dataclass
class Plane:
    name: str
    lines: dict        # line name -> [(metadata id, start ns, dur ns)]
    meta: dict         # event metadata id -> (name, {stat name: value})


def _plane(buf, keep_line, keep_event) -> Plane:
    name, lines, raw_meta, stat_names = "", [], {}, {}
    for f, _, v, _, _ in fields(buf):
        if f == 2:
            name = text(v)
        elif f == 3:
            lines.append(v)
        elif f in (4, 5):
            key, val = 0, None
            for ef, _, ev, _, _ in fields(v):
                if ef == 1:
                    key = ev
                elif ef == 2:
                    val = ev
            if val is None:
                continue
            mname, stats = "", []
            for mf, _, mv, _, _ in fields(val):
                if mf == 2:
                    mname = text(mv)
                elif mf == 5 and f == 4:
                    stats.append(mv)
            if f == 4:
                raw_meta[key] = (mname, stats)
            else:
                stat_names[key] = mname

    def value(v):
        if isinstance(v, tuple):
            return stat_names.get(v[1], "")
        return v

    meta = {}
    for k, (mname, stats) in raw_meta.items():
        st = {}
        for s in stats:
            mid, v = _stat(s)
            st[stat_names.get(mid, str(mid))] = value(v)
        meta[k] = (mname, st)
    wanted = {k for k, (m, _) in meta.items() if keep_event(name, m)}
    out = {}
    for lb in lines:
        lname, ts, events = "", 0, []
        for f, _, v, _, _ in fields(lb):
            if f == 2:
                lname = text(v)
            elif f == 3:
                ts = v
            elif f == 4:
                events.append(v)
        if not keep_line(name, lname):
            continue
        rows = out.setdefault(lname, [])
        for eb in events:
            # metadata_id is an event's first field: skip unwanted
            # events before decoding the rest
            key, i = _varint(eb, 0)
            if key == 8 and _varint(eb, i)[0] not in wanted:
                continue
            mid = off = dur = 0
            for f, _, v, _, _ in fields(eb):
                if f == 1:
                    mid = v
                elif f == 2:
                    off = v
                elif f == 3:
                    dur = v
            # whole nanoseconds, as jax.profiler.ProfileData gives them
            rows.append((mid, float(ts + off // 1000), float(dur // 1000)))
    return Plane(name, out, meta)


def host_span(name: str) -> bool:
    """The harness's spans and the program's ``fl.*`` spans."""
    return name in tr.SPANS or name.startswith("fl.")


METADATA_PLANE = "/host:metadata"    # one event metadata per program
HLO_STAT = "Hlo Proto"                # its optimized HloProto, serialized


def wanted_line(plane: str, line: str) -> bool:
    if plane.startswith(tr.DEVICE_PREFIX):
        return line == tr.OPS_LINE
    return plane == tr.HOST_PLANE


def wanted_event(plane: str, name: str) -> bool:
    return plane != tr.HOST_PLANE or host_span(name)


def read_xspace(path, keep_line=wanted_line, keep_event=wanted_event):
    """The planes of a trace file, each line's events as (metadata id,
    start ns, duration ns); the lines ``keep_line(plane, line)`` and the
    events ``keep_event(plane, metadata name)`` refuse are left out."""
    with open(path, "rb") as fh:
        buf = memoryview(fh.read())
    return [_plane(v, keep_line, keep_event)
            for f, _, v, _, _ in fields(buf) if f == 1]


# ----------------------------------------------------------------------
# The scoped trace
# ----------------------------------------------------------------------

# ----------------------------------------------------------------------
# Ops XLA made without metadata
# ----------------------------------------------------------------------
# XLA gives no op_name to the ops it adds while lowering (the copies and
# pads that lay a tensor out anew for a reshape, the dynamic-update-slices
# a concatenate becomes, loop fusions rooted at them); the profiler then
# gives such an op the path of the loop around it, which names no stage.
# The trace carries each program's optimized HLO, so such an op is
# charged by the graph: a fusion to the stage most of its fused
# instructions carry, a dynamic-update-slice to the stage of the update it
# writes (else of the buffer it writes into), any other op to the stage of
# the first op that reads its result and has one.

@dataclass
class Instr:
    name: str = ""
    opcode: str = ""
    op_name: str = ""
    operands: tuple = ()
    calls: tuple = ()


def _ids(wt: int, v) -> list:
    if wt != 2:
        return [v]
    out, i = [], 0
    while i < len(v):
        x, i = _varint(v, i)
        out.append(x)
    return out


def hlo_graph(buf):
    """(instructions by id, instruction ids by computation id) of one
    serialized HloProto (``hlo_module`` 1; its ``computations`` 3, each
    with ``instructions`` 2 and ``id`` 5; an instruction's ``name`` 1,
    ``opcode`` 2, ``metadata`` 7 (``op_name`` 2), ``id`` 35,
    ``operand_ids`` 36, ``called_computation_ids`` 38)."""
    instrs, comps = {}, {}
    mod = next((v for f, _, v, _, _ in fields(buf) if f == 1), b"")
    for f, _, comp, _, _ in fields(mod):
        if f != 3:
            continue
        cid, members = 0, []
        for g, _, v, _, _ in fields(comp):
            if g == 5:
                cid = v
            elif g == 2:
                ins, iid, ops, calls = Instr(), 0, [], []
                for h, wt, x, _, _ in fields(v):
                    if h == 1:
                        ins.name = text(x)
                    elif h == 2:
                        ins.opcode = text(x)
                    elif h == 7:
                        ins.op_name = next((text(y) for k, _, y, _, _
                                            in fields(x) if k == 2), "")
                    elif h == 35:
                        iid = x
                    elif h == 36:
                        ops += _ids(wt, x)
                    elif h == 38:
                        calls += _ids(wt, x)
                ins.operands, ins.calls = tuple(ops), tuple(calls)
                instrs[iid] = ins
                members.append(iid)
        comps[cid] = members
    return instrs, comps


def graph_stages(instrs: dict, comps: dict) -> dict:
    """The stage of each instruction XLA made without metadata, by name
    (None where the graph gives none), found by propagating stages along
    the graph until nothing changes."""
    users: dict = {}
    for i, ins in instrs.items():
        for o in ins.operands:
            users.setdefault(o, []).append(i)
    st: dict = {}
    for i, ins in instrs.items():
        if ins.op_name:
            st[i] = scope_of(ins.op_name)
        elif ins.opcode == "fusion":
            count: dict = {}
            for c in ins.calls:
                for j in comps.get(c, ()):
                    s = scope_of(instrs[j].op_name)
                    if s:
                        count[s] = count.get(s, 0) + 1
            if count:
                st[i] = max(count, key=count.get)
    open_ = [i for i, ins in instrs.items()
             if not ins.op_name and st.get(i) is None]
    changed = True
    while changed:
        changed = False
        for i in open_:
            if st.get(i) is not None:
                continue
            ins = instrs[i]
            if ins.opcode == "dynamic-update-slice":
                # the update it writes, else the buffer it writes into
                look = ins.operands[1:2] + ins.operands[:1]
            else:
                look = users.get(i, ())
            s = next((st[j] for j in look if st.get(j)), None)
            if s:
                st[i] = s
                changed = True
    return {ins.name: st.get(i) for i, ins in instrs.items()
            if not ins.op_name}


@dataclass
class ScopedOp(tr.Op):
    tf_op: str = ""
    scope: Optional[str] = None


def load(path) -> tr.Trace:
    """The trace as ``bench.trace.load`` reduces it, each device op a
    :class:`ScopedOp` with its stage, and the program's host spans kept
    beside the harness's."""
    t = tr.Trace()
    planes = read_xspace(path)
    inferred = {}                  # program id -> {instruction: stage}
    for p in planes:
        if p.name == METADATA_PLANE:
            for pid, (_, stats) in p.meta.items():
                if isinstance(stats.get(HLO_STAT), bytes):
                    inferred[pid] = graph_stages(
                        *hlo_graph(memoryview(stats[HLO_STAT])))
    for p in planes:
        if p.name.startswith(tr.DEVICE_PREFIX):
            ops = []
            for mid, s, d in p.lines.get(tr.OPS_LINE, []):
                hlo, stats = p.meta.get(mid, ("", {}))
                if tr.opcode(hlo) in tr.CONTAINERS:
                    continue
                tf_op = str(stats.get("tf_op", ""))
                st = scope_of(tf_op)
                if st is None:
                    st = inferred.get(stats.get("program_id"), {}).get(
                        hlo.partition(" = ")[0].lstrip("%"))
                ops.append(ScopedOp(hlo, s, d, tf_op, st))
            t.devices[p.name] = ops
        elif p.name == tr.HOST_PLANE:
            for rows in p.lines.values():
                t.spans += [(p.meta[mid][0], s, s + d) for mid, s, d in rows]
    t.spans.sort(key=lambda x: x[1])
    return t


def same_ops(a: tr.Trace, b: tr.Trace) -> bool:
    """Whether two reductions hold the same device ops at the same
    times."""
    return set(a.devices) == set(b.devices) and all(
        [(x.start, x.dur) for x in ops]
        == [(y.start, y.dur) for y in b.devices[k]]
        for k, ops in a.devices.items())


def for_context(ctx) -> Optional[tr.Trace]:
    """The scoped load of the trace a metric's context reduces: the file
    ``ctx.trace_path`` names, else the newest of the harness's trace
    files whose device ops match ``ctx.trace``; None if none does.  Read
    once per context and kept on it."""
    if not hasattr(ctx, "scoped_trace"):
        ctx.scoped_trace = _find(ctx)
    return ctx.scoped_trace


def _find(ctx) -> Optional[tr.Trace]:
    path = getattr(ctx, "trace_path", None)
    if path is not None:
        return load(path)
    pat = os.path.join(tempfile.gettempdir(), TRACE_DIRS, "**",
                       "*.xplane.pb")
    for p in sorted(glob.glob(pat, recursive=True), key=os.path.getmtime,
                    reverse=True):
        try:
            t = load(p)
        except (OSError, ValueError, IndexError, struct.error):
            continue                # a file another run left half written
        if same_ops(t, ctx.trace):
            return t
    return None


def split(trace: tr.Trace, lo: float, hi: float) -> dict:
    """Device seconds per stage (``None``: unscoped) of the ops that
    start inside [lo, hi], averaged over the chips."""
    out: dict = {}
    n = max(len(trace.devices), 1)
    for ops in trace.devices.values():
        for o in ops:
            if lo <= o.start <= hi:
                out[o.scope] = out.get(o.scope, 0.0) + o.dur * 1e-9 / n
    return out


def scope_seconds(ctx, name: str):
    """Device seconds of the stage ``name`` inside the context's window,
    averaged over the chips: 0 where XLA fused all of the stage's work
    into other stages' ops, None where no op of the trace carries any
    stage (a program without the stage scopes)."""
    t = for_context(ctx)
    by = split(t, ctx.lo, ctx.hi) if t is not None else {}
    if not any(k is not None for k in by):
        return None
    return by.get(name, 0.0)


def ms_per_round(ctx, name: str):
    """Device milliseconds per round of the stage ``name``; None where
    the program names no stage or no round ran."""
    secs = scope_seconds(ctx, name)
    if secs is None or ctx.rounds == 0:
        return None
    return 1e3 * secs / ctx.rounds


# ----------------------------------------------------------------------
# The script
# ----------------------------------------------------------------------

def report(trace: tr.Trace, lo=None, hi=None) -> dict:
    """The stage split of one trace: seconds per stage, the unscoped
    remainder's top ops, each Pallas kernel's stage, and the idle gaps
    labelled by the host span that holds them."""
    if lo is None:
        lo, hi = trace.window()
    ops = [o for v in trace.devices.values() for o in v]
    n = max(len(trace.devices), 1)
    busy = sum(tr.busy_ns(v, lo, hi) for v in trace.devices.values()) / n
    by = split(trace, lo, hi)
    kernels: dict = {}
    for o in ops:
        if tr.custom_call(o.name) is not None and lo <= o.start <= hi:
            k = kernels.setdefault(tr.op_name(o.name), {})
            k[o.scope] = k.get(o.scope, 0.0) + o.dur * 1e-9 / n
    gaps = sorted(tr.gaps(ops, lo, hi), key=lambda g: g[0] - g[1])[:10]
    return {
        "window_s": (hi - lo) * 1e-9, "busy_s": busy * 1e-9,
        "stages_s": {str(k): v for k, v in sorted(
            by.items(), key=lambda kv: -kv[1])},
        "scoped_share": 1.0 - by.get(None, 0.0) / max(busy * 1e-9, 1e-30),
        "unscoped_top": [[name, t * 1e-9 / n] for name, t in tr.top_ops(
            [o for o in ops if o.scope is None], lo, hi)],
        "kernels": {k: {str(s): v for s, v in d.items()}
                    for k, d in kernels.items()},
        "idle_gaps": [[tr.label(trace.spans, (s + e) / 2), (e - s) * 1e-9]
                      for s, e in gaps]}


def _setup_split(records) -> dict:
    spans = {r["name"]: r["dur"] for r in records if r["type"] == "span"}
    comp = [r for r in records if r.get("kind") == "compile"]
    return {"fl.federation_s": spans.get("fl.federation"),
            "fl.engine_s": spans.get("fl.engine"),
            "compiles": len(comp),
            "compile_s": sum(r["dur"] for r in comp),
            "cache_hits": sum(bool(r["cache_hit"]) for r in comp)}


def measure(name: str, seed: int, seconds: float, out_dir=None) -> dict:
    """Set-up under the recorder, then windows off, on, off, on; the
    last window's trace is copied to ``out_dir`` when one is given."""
    import contextlib
    import json
    import shutil
    import time

    from . import run, spec, system
    started = run.process_start()
    run.program_on_path()
    bench = spec.benchmark()
    w = spec.workload(name, bench)
    devices = run.devices_for(int(w["chips"]))
    peaks = spec.peaks(devices[0].device_kind)
    from repro.compile_cache import enable_compile_cache
    from repro.fl import telemetry
    enable_compile_cache()
    with telemetry.recording() as rec:
        cell = system.Cell(spec.config(w["config"], bench),
                           spec.traffic(w["traffic"]),
                           spec.config_module(w["config"]))
        params, key, _ = cell.prime(seed)
    out = {"workload": name, "seed": seed,
           "setup_s": time.time() - started,
           "setup": _setup_split(rec.snapshot()), "windows": []}
    run.log(f"[{name}] set-up {json.dumps(out['setup'])}")
    prof = tempfile.mkdtemp(prefix="scopes-")
    try:
        for i, on in enumerate((False, True, False, True)):
            shutil.rmtree(prof, ignore_errors=True)
            with telemetry.recording() if on else contextlib.nullcontext():
                params, key, st = run.window(cell, params, key, seconds,
                                             prof if on else None)
            rounds = st["attempted"] - st["failed"]
            out["windows"].append({"traced": on, "rounds": rounds,
                                   "window_s": st["window_s"],
                                   "rounds_per_s": rounds / st["window_s"]})
            run.log(f"[{name}] window {i} traced={on}: "
                    f"{rounds / st['window_s']:.6f} rounds/s")
        path = glob.glob(os.path.join(prof, "**", "*.xplane.pb"),
                         recursive=True)[0]
        ctx = run.Context(tr.load(path), cell, rounds, peaks, len(devices))
        ctx.trace_path = path
        out["split"] = report(for_context(ctx), ctx.lo, ctx.hi)
        out["metrics"] = {m["name"]: spec.metric_module(m["name"]).read(ctx)
                          for m in spec.per_layer_for(name, bench)}
        if out_dir is not None:
            os.makedirs(out_dir, exist_ok=True)
            out["trace"] = os.path.join(out_dir, f"{name}-{seed}.xplane.pb")
            shutil.copyfile(path, out["trace"])
    finally:
        shutil.rmtree(prof, ignore_errors=True)
    return out


def main(argv=None) -> int:
    import argparse
    import json
    import sys
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--trace")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", help="keep the last window's trace here")
    args = ap.parse_args(argv)
    if args.trace:
        out = report(load(args.trace))
    elif args.workload:
        out = measure(args.workload, args.seed, args.seconds, args.out)
    else:
        ap.error("give --trace or --workload")
    print(json.dumps(out), file=sys.stdout, flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
