"""Device milliseconds per round of Step 5's fold of the kept updates and
the model update (the fold kernel where the cell runs it): the summed
device time of the ops in the program's ``step5_fold`` stage inside the
traced window, over the rounds that ran (``bench/scopes.py``).  0 where
XLA fused all of the stage's work into other stages' ops; nothing where
the program names no stages."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_round(ctx, "step5_fold")
