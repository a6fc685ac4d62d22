"""Device milliseconds per round of Step 2's local SGD on the clients,
forward and backward (``engine.make_round_body``'s ``client_update``):
the summed device time of the ops in the program's ``client_sgd`` stage
inside the traced window, over the rounds that ran
(``bench/scopes.py``).  0 where XLA fused all of the stage's work into
other stages' ops; nothing where the program names no stages."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_round(ctx, "client_sgd")
