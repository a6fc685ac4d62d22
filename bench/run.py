"""Run one benchmark cell on the chip and print its result line.

    python -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (timed from process start as ``setup_s``): the compile cache,
the cell's federation and engine (the data set from the traffic file),
the weights from ``--seed`` on the device, and two calls of the
window's program that take the weights through their first three
rounds.  The window then calls ``RoundEngine.run_training`` back to
back for ``--seconds`` (closed loop): each call runs ``rounds_per_call``
rounds and one eval and ends in one host sync; the weights and the key
carry from call to call.  With ``--trace 1`` a profiler records the
window (at most ``TRACE_SECONDS`` of it) and the per-layer metrics are
read from the trace; otherwise the end-to-end metrics are reported.
After the window the program's state is freed and the plain reference
(``bench/reference.py``) follows the three set-up rounds; ``correct``
is the comparison of the two (``bench/correct.py``).

The last line of standard output is the result as one JSON object.
Without a TPU, or with fewer chips than the cell asks for, the run
exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_IMPORT = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402


from . import correct, spec, system  # noqa: E402
from . import reference as ref  # noqa: E402
from . import trace as tr_  # noqa: E402
from . import traffic as tr  # noqa: E402

TRACE_SECONDS = 10.0
GIB = 1 << 30


def process_start() -> float:
    """The wall-clock time this process started, from /proc."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return time.time() - (up - int(fields[19]) / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return T_IMPORT


def program_on_path() -> None:
    """Make the program under ``src/`` importable, as the repo's own
    entry points do."""
    src = str(spec.REPO_DIR / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class NoDevice(RuntimeError):
    pass


def devices_for(chips: int):
    """The TPU devices of this run; NoDevice without enough of them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX's devices are {devs[0].platform}; "
                       f"the benchmark runs on the chip only")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX finds "
                       f"{len(devs)}")
    return devs[:chips]


def peak_bytes(device) -> int:
    """Live buffers or the runtime's reservation for them and for the
    programs' temporaries, whichever peaked higher."""
    stats = device.memory_stats() or {}
    return max(int(stats.get("peak_bytes_in_use", 0)),
               int(stats.get("peak_bytes_reserved", 0)))


class Context:
    """What a per-layer metric's ``read(ctx)`` sees of a traced run."""

    def __init__(self, trace, cell, rounds, peaks, chips):
        self.trace, self.rounds, self.peaks, self.chips = (trace, rounds,
                                                           peaks, chips)
        self.conf, self.traffic, self.cfgmod = (cell.conf, cell.traffic,
                                                cell.cfgmod)
        self.n_params = cell.n_params
        self.sealed = tr.sealed_count(cell.traffic)
        self.lo, self.hi = trace.window()
        self.window_s = (self.hi - self.lo) * 1e-9
        ops = list(trace.devices.values())
        self.busy_s = (sum(tr_.busy_ns(o, self.lo, self.hi) for o in ops)
                       / max(len(ops), 1) * 1e-9)

    def ops(self):
        return [o for v in self.trace.devices.values() for o in v]

    def kernel_seconds(self, match) -> float:
        """Summed device seconds of the Pallas ops whose (output,
        operands) signature ``match`` accepts, inside the window."""
        tot = 0.0
        for o in self.ops():
            sig = tr_.custom_call(o.name)
            if sig is not None and match(sig) and self.lo <= o.start <= self.hi:
                tot += o.dur
        return tot * 1e-9

    def breakdown(self) -> dict:
        ops = self.ops()
        idle = sorted(tr_.gaps(ops, self.lo, self.hi),
                      key=lambda g: g[0] - g[1])[:10]
        return {"device_ops": [[n, t * 1e-9] for n, t in
                               tr_.top_ops(ops, self.lo, self.hi)],
                "idle_gaps": [[tr_.label(self.trace.spans, (s + e) / 2),
                               (e - s) * 1e-9] for s, e in idle]}


def window(cell, params, key, seconds: float, profile_dir=None):
    """Closed-loop calls for ``seconds``; returns (params, key, stats)."""
    import jax
    from repro.fl import trace_counter
    R, lrs = cell.R, cell.lrs["window"]
    stats = {"attempted": 0, "failed": 0, "calls": 0}
    if profile_dir is not None:
        jax.profiler.start_trace(profile_dir)
    try:
        with trace_counter() as tc, jax.profiler.TraceAnnotation("window"):
            t0 = time.perf_counter()
            while True:
                stats["attempted"] += R
                try:
                    with jax.profiler.TraceAnnotation("dispatch"):
                        params, key, met = cell.call(params, key, lrs)
                        fin = system.all_finite(params)
                    with jax.profiler.TraceAnnotation("sync"):
                        met, fin = jax.device_get((met, fin))
                except Exception as e:          # the state is lost
                    log(f"call {stats['calls']} raised {type(e).__name__}: "
                        f"{e}")
                    stats["failed"] += R
                    params = None
                    break
                stats["calls"] += 1
                if not (bool(fin) and system.metrics_finite(met)):
                    stats["failed"] += R
                if time.perf_counter() - t0 >= seconds:
                    break
            stats["window_s"] = time.perf_counter() - t0
        stats["compiles"] = tc.total()
    finally:
        if profile_dir is not None:
            jax.profiler.stop_trace()
    return params, key, stats


def cache_entries(path: str) -> int:
    """Files in the persistent compile cache: a set-up that adds some
    compiled a program that the cache did not hold."""
    try:
        return sum(1 for e in os.scandir(path) if e.is_file())
    except OSError:
        return 0


def run_named(name: str, seed: int, seconds: float, trace: bool,
              devices, peaks, bench=None, started=None) -> dict:
    """One run of the cell ``name`` of ``BENCHMARK.json``."""
    bench = bench or spec.benchmark()
    w = spec.workload(name, bench)
    return run_cell(name, spec.config(w["config"], bench),
                    spec.traffic(w["traffic"]), spec.config_module(w["config"]),
                    seed, seconds, trace, devices, peaks,
                    per_layer=spec.per_layer_for(name, bench),
                    end_to_end=spec.end_to_end_for(name, bench),
                    started=started)


def run_cell(name: str, conf: dict, traffic: dict, cfgmod, seed: int,
             seconds: float, trace: bool, devices, peaks, *, per_layer,
             end_to_end, started=None) -> dict:
    """One run of a cell; returns the result object."""
    import jax
    started = process_start() if started is None else started
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    cached = cache_entries(cache)
    log(f"[{name}] seed {seed}, compile cache {cache} ({cached} entries)")

    log(f"[{name}] imports and devices {time.time() - started:.3f} s")
    cell = system.Cell(conf, traffic, cfgmod)
    log(f"[{name}] federation and engine {time.time() - started:.3f} s")
    params, key, prog = cell.prime(seed)
    setup_s = time.time() - started
    log(f"[{name}] set-up {setup_s:.3f} s ({cell.n_params:,} params, "
        f"{cell.R} rounds per call, {cache_entries(cache) - cached} "
        f"files written to the compile cache)")

    prof = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        params, key, st = window(cell, params, key,
                                 min(seconds, TRACE_SECONDS) if trace
                                 else seconds, prof)
        log(f"[{name}] window: {st['calls']} calls, {st['attempted']} rounds "
            f"in {st['window_s']:.3f} s, {st['failed']} failed")
        log(f"[{name}] compiles inside the window: {st['compiles']}")
        peak = max(peak_bytes(d) for d in devices)
        layer = None
        if trace:
            import glob
            path = glob.glob(os.path.join(prof, "**", "*.xplane.pb"),
                             recursive=True)[0]
            ctx = Context(tr_.load(path), cell, st["attempted"] - st["failed"],
                          peaks, len(devices))
            layer = {}
            for m in per_layer:
                v = spec.metric_module(m["name"]).read(ctx)
                if v is not None:
                    layer[m["name"]] = {"value": v, "unit": m["unit"]}
            busy = {"busy_s": ctx.busy_s, "window_s": ctx.window_s}
            breakdown = ctx.breakdown()
    finally:
        if prof is not None:
            shutil.rmtree(prof, ignore_errors=True)

    # free the program's state before the reference runs
    data = cell.data
    del params, cell
    gc.collect()
    jax.clear_caches()
    t0 = time.perf_counter()
    reads = ref.Reference(cfgmod, conf, traffic, data).readings(seed)
    log(f"[{name}] reference {time.perf_counter() - t0:.3f} s (rounds "
        + ", ".join(f"{x:.3f}" for x in reads["seconds"]) + " s)")
    nums = correct.numbers(prog, reads)
    ok, compared = correct.judge(nums, traffic["limits"])
    ok = ok and prog["finite"]
    margin = correct.threshold_margin(reads["c1c2"], traffic["eps"])
    log(f"[{name}] nearest C1*C2 to a keep threshold: {margin:.4f} of it; "
        f"smallest |cos(update, guide)|, whose sign is C1: "
        f"{abs(reads['cos']).min():.4g}")

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if trace:
        metrics = layer
        device.update(busy)
    else:
        rounds = st["attempted"] - st["failed"]
        metrics = {"rounds_per_s": {"value": rounds / st["window_s"],
                                    "unit": "rounds/s"},
                   "peak_hbm_gib": {"value": peak / GIB, "unit": "GiB"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
        wanted = {m["name"] for m in end_to_end}
        metrics = {k: v for k, v in metrics.items() if k in wanted}
    out = {"correct": bool(ok), "attempted": st["attempted"],
           "failed": st["failed"], "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = breakdown
    for k in correct.NAMES:
        if k not in compared:
            log(f"[{name}] {k} {nums[k]:.6g} (not compared)")
    for k, v in compared.items():
        log(f"[{name}] {k} {v['value']:.6g} (limit {v['limit']:g})")
    out["compared"] = compared
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    started = process_start()
    program_on_path()
    try:
        bench = spec.benchmark()
        w = spec.workload(args.workload, bench)
        devices = devices_for(int(w["chips"]))
        peaks = spec.peaks(devices[0].device_kind)
    except (NoDevice, KeyError, OSError, ImportError) as e:
        log(f"bench.run: {e}")
        return 2
    out = run_named(args.workload, args.seed, args.seconds,
                    bool(args.trace), devices, peaks, bench, started)
    if "repro.launch.dryrun" in sys.modules:
        log("bench.run: the run imported repro.launch.dryrun, which "
            "rewrites XLA_FLAGS")
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
