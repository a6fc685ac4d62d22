"""The routed experts' grouped products (megablox ``gmm`` and ``tgmm``,
the Pallas calls ``models/moe.apply_expert_share`` makes) against their
roofline: the least time the chip needs for 3 x the forward products'
operations and bytes at the expected routed rows (the configuration's
``expert_work``: every token's slots times held over published experts)
of every client and enclave SGD step in the window, the larger of
operations over the bf16 peak and bytes over the HBM bandwidth, over the
summed device time of the calls in the ``client_sgd`` and ``guide_sgd``
stages.  The calls are found by their Pallas name; recomputation under
remat is in the time and not in the work.  Nothing where the program
makes no such call."""
import re

from bench import scopes

_NAME = re.compile(r"%?t?gmm\.\d+ = ")
STAGES = ("client_sgd", "guide_sgd")


def read(ctx):
    t = scopes.for_context(ctx)
    work = getattr(ctx.cfgmod, "expert_work", None)
    if t is None or work is None or ctx.rounds == 0:
        return None
    secs = sum(o.dur for ops in t.devices.values() for o in ops
               if _NAME.match(o.name) and o.scope in STAGES
               and ctx.lo <= o.start <= ctx.hi) * 1e-9 / max(len(t.devices), 1)
    if not secs:
        return None
    tr = ctx.traffic
    steps = tr["n_clients"] * tr["local_steps"] * ctx.rounds
    flops, nbytes = (3.0 * steps * (a + b) for a, b in zip(
        work(ctx.conf, tr, tr["batch_size"]), work(ctx.conf, tr, ctx.sealed)))
    least = max(flops / ctx.peaks["bf16_flops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / secs
