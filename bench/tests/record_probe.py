"""Record ``data/scoped.xplane.pb`` on the chip: three calls of a tiny
scoped round, as the harness calls the window's program.

    python -m bench.tests.record_probe bench/tests/data/scoped.xplane.pb

The round is the program's dense DiverseFL round on 10 clients of a
softmax regression (1,290 float32 weights) with 2 sign-flippers, the
Pallas similarity and fold kernels, clients mapped in chunks of 4 (so
the last chunk is padded), 2 rounds and one eval per call.  Each call
runs inside the harness's ``dispatch`` span and is fetched inside its
``sync`` span, all inside ``window``; the program adds ``fl.prepare``
and ``fl.launch``.  The written file keeps the device planes' ``XLA
Ops`` line with each op's HLO text, ``tf_op`` and ``program_id``, and
of the host plane the harness's and the program's spans, so it stays a
few tens of KB; the programs' HLO (the ``/host:metadata`` plane) is
left out.
"""
from __future__ import annotations

import glob
import os
import sys
import tempfile

from bench import run, scopes

N_CLIENTS, F, DIM, N_CLASSES, CHUNK, ROUNDS = 10, 2, 128, 10, 4, 2
KEEP_STATS = ("tf_op", "program_id")


def varint(x: int) -> bytes:
    out = bytearray()
    while True:
        b = x & 0x7F
        x >>= 7
        if x:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def field(f: int, payload: bytes) -> bytes:
    return varint(f << 3 | 2) + varint(len(payload)) + payload


def _first(buf, field: int, default=0):
    return next((v for f, _, v, _, _ in scopes.fields(buf) if f == field),
                default)


def _keep_plane(buf, keep_line, keep_event) -> bytes:
    """One XPlane with the lines ``keep_line`` refuses dropped, the
    events ``keep_event`` refuses dropped, and of the event metadata only
    what the kept events use, each with its name and its ``tf_op`` and
    ``program_id`` stats."""
    parts = list(scopes.fields(buf))
    name = scopes.text(_first(buf, 2, b""))
    stat_ids = set()
    for f, _, v, _, _ in parts:
        if f == 5:
            sm = _first(v, 2, b"")
            if scopes.text(_first(sm, 2, b"")) in KEEP_STATS:
                stat_ids.add(_first(v, 1))
    meta = {}
    for f, _, v, _, _ in parts:
        if f == 4:
            em = _first(v, 2, b"")
            kept = bytearray()
            for mf, _, x, ms, me in scopes.fields(em):
                if mf in (1, 2) or (mf == 5 and _first(x, 1) in stat_ids):
                    kept += em[ms:me]
            meta[_first(v, 1)] = (scopes.text(_first(em, 2, b"")),
                                  field(4, varint(1 << 3) + varint(
                                      _first(v, 1)) + field(2, bytes(kept))))
    used, out = set(), bytearray()
    for f, _, v, s, e in parts:
        if f == 3:
            lfields = list(scopes.fields(v))
            lname = scopes.text(_first(v, 2, b""))
            if not keep_line(name, lname):
                continue
            line, kept = bytearray(), 0
            for lf, _, x, ls, le in lfields:
                if lf == 4:
                    mid = _first(x, 1)
                    if not keep_event(name, meta.get(mid, ("",))[0]):
                        continue
                    used.add(mid)
                    kept += 1
                line += v[ls:le]
            if kept:
                out += field(3, bytes(line))
        elif f not in (4, 6):
            out += buf[s:e]
    for mid in sorted(used):
        out += meta[mid][1]
    return bytes(out)


def shrink(src: str, dst: str) -> None:
    """Write ``src``'s device ops and program and harness spans to
    ``dst``."""
    with open(src, "rb") as fh:
        buf = memoryview(fh.read())
    out = bytearray()
    for f, _, v, _, _ in scopes.fields(buf):
        if f != 1:
            continue
        name = scopes.text(_first(v, 2, b""))
        if name.startswith(scopes.tr.DEVICE_PREFIX) or \
                name == scopes.tr.HOST_PLANE:
            out += field(1, _keep_plane(v, scopes.wanted_line,
                                         scopes.wanted_event))
    with open(dst, "wb") as fh:
        fh.write(bytes(out))


def record(dst: str) -> None:
    run.program_on_path()
    import jax
    from repro.core.attacks import AttackConfig
    from repro.data import (FederatedData, make_classification,
                            partition_sorted_shards)
    from repro.fl import (FLConfig, Federation, RoundEngine,
                          softmax_regression)
    x, y = make_classification(jax.random.PRNGKey(0), N_CLIENTS * 16,
                               N_CLASSES, DIM)
    data = FederatedData.from_partitions(
        partition_sorted_shards(x, y, N_CLIENTS), N_CLASSES)
    tx, ty = make_classification(jax.random.PRNGKey(9), 64, N_CLASSES, DIM)
    cfg = FLConfig(n_clients=N_CLIENTS, f=F, rounds=ROUNDS, batch_size=4,
                   eval_every=ROUNDS, l2=0.0, client_chunk=CHUNK,
                   attack=AttackConfig(kind="sign_flip"),
                   use_kernel_stats=True, use_kernel_agg=True)
    model = softmax_regression(input_dim=DIM, n_classes=N_CLASSES)
    fed = Federation.create(model, data, tx, ty, cfg, jax.random.PRNGKey(2))
    engine = RoundEngine(model, fed, cfg)
    params = model.init(jax.random.PRNGKey(1))
    key = jax.random.PRNGKey(3)
    lrs = [0.1] * ROUNDS
    params, key, met, _ = engine.run_training(params, key, lrs)  # compile
    jax.block_until_ready((params, met))
    prof = tempfile.mkdtemp(prefix="probe-")
    jax.profiler.start_trace(prof)
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("dispatch"):
                params, key, met, _ = engine.run_training(params, key, lrs)
            with jax.profiler.TraceAnnotation("sync"):
                jax.device_get(met)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(prof, "**", "*.xplane.pb"),
                    recursive=True)[0]
    shrink(src, dst)
    print(f"{dst}: {os.path.getsize(dst)} bytes (from {os.path.getsize(src)})")


if __name__ == "__main__":
    record(sys.argv[1])
