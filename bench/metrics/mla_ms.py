"""Device milliseconds per round of the multi-head latent attention
(``models/attention.mla_attention``, scope ``mla``): the summed device
time of the ops whose scope path (``tf_op``) carries ``mla``, inside the
traced window, over the rounds that ran; client and guide SGD, forward
and backward, and the eval forward alike.  Ops XLA made without
metadata are not counted.  Nothing where no op carries the scope."""
from bench import scopes


def scope_ms(ctx, name: str):
    """Device ms per round of the ops whose scope path holds ``name``,
    averaged over the chips; None where no op does."""
    t = scopes.for_context(ctx)
    if t is None or ctx.rounds == 0:
        return None
    secs, found = 0.0, False
    for ops in t.devices.values():
        for o in ops:
            if name in scopes.scope_path(o.tf_op):
                found = True
                if ctx.lo <= o.start <= ctx.hi:
                    secs += o.dur * 1e-9
    if not found:
        return None
    return 1e3 * secs / max(len(t.devices), 1) / ctx.rounds


def read(ctx):
    return scope_ms(ctx, "mla")
