"""Device milliseconds per round of building the (n, D) update and guide
rows, with the padding, stacking and unblocking of chunked client maps:
the summed device time of the ops in the program's ``flatten`` stage
inside the traced window, over the rounds that ran
(``bench/scopes.py``).  0 where XLA fused all of the stage's work into
other stages' ops; nothing where the program names no stages."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_round(ctx, "flatten")
