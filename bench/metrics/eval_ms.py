"""Device milliseconds per round of the eval tail of each call
(``fl/metrics.make_eval_fn``): the summed device time of the ops in the
program's ``eval`` stage inside the traced window, over the rounds that
ran (``bench/scopes.py``).  0 where XLA fused all of the stage's work
into other stages' ops; nothing where the program names no stages."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_round(ctx, "eval")
