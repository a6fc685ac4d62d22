"""Step 5's Pallas masked fold (``kernels/masked_agg.py``) against its
HBM roofline: the bytes Step 5 needs each round (every client's float32
update read once, the (D,) float32 accumulator read and written once
per fold call: once a round on the dense path, once per client block
when streaming) over the chip's HBM bandwidth, divided by the kernel's
summed device time in the traced window.  Its ops are the Pallas calls
with a (1, D) float32 output and an (n, D) float32 update operand."""


def _is_kernel(sig, d):
    (odt, out), args = sig
    return (odt == "f32" and out == (1, d) and len(args) == 3
            and args[1][0] == "f32" and args[1][1][-1] == d)


def read(ctx):
    t, d = ctx.traffic, ctx.n_params
    secs = ctx.kernel_seconds(lambda sig: _is_kernel(sig, d))
    if not secs or ctx.rounds == 0:
        return None
    n = t["n_clients"]
    calls = -(-n // t["client_chunk"]) if t["streaming"] else 1
    need = (n * d * 4 + calls * 2 * d * 4) * ctx.rounds
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / secs
