"""Step 4's Pallas similarity kernel (``kernels/similarity.py``) against
its HBM roofline: the bytes Step 4 needs each round (the (N, D) float32
update and guide matrices read once) over the chip's HBM bandwidth,
divided by the kernel's summed device time in the traced window.  Its
ops are the Pallas calls with two (n, D) float32 operands and an
(n, 128) float32 output."""


def _is_kernel(sig, d):
    (odt, out), args = sig
    return (odt == "f32" and len(out) == 2 and out[1] == 128
            and len(args) == 2
            and all(dt == "f32" and a[-1] == d for dt, a in args))


def read(ctx):
    d = ctx.n_params
    secs = ctx.kernel_seconds(lambda sig: _is_kernel(sig, d))
    if not secs or ctx.rounds == 0:
        return None
    need = 2 * ctx.traffic["n_clients"] * d * 4 * ctx.rounds
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / secs
