"""Model FLOP utilization of the whole round: the FLOPs that the clients'
and the enclave's local SGD require (3 x forward, recomputation not
counted) for every round that ran in the traced window, over the
window's length times the chip's bf16 peak."""


def read(ctx):
    t = ctx.traffic
    fwd = ctx.cfgmod.forward_flops(ctx.conf, t)
    examples = t["n_clients"] * t["local_steps"] * (
        t["batch_size"] + ctx.sealed)
    flops = 3.0 * fwd * examples * ctx.rounds
    if ctx.window_s <= 0 or ctx.rounds == 0:
        return None
    return 100.0 * flops / (ctx.window_s * ctx.chips
                            * ctx.peaks["bf16_flops_per_s"])
