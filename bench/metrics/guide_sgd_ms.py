"""Device milliseconds per round of Step 3's guiding updates in the enclave
(``SecureServer.compute_guides``): the summed device time of the ops in
the program's ``guide_sgd`` stage inside the traced window, over the
rounds that ran (``bench/scopes.py``).  0 where XLA fused all of the
stage's work into other stages' ops; nothing where the program names no
stages."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_round(ctx, "guide_sgd")
