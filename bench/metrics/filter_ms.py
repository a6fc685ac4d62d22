"""Device milliseconds per round of Step 4's C1/C2 statistics and keep mask
(the similarity kernel where the cell runs it): the summed device time
of the ops in the program's ``step4_filter`` stage inside the traced
window, over the rounds that ran (``bench/scopes.py``).  0 where XLA
fused all of the stage's work into other stages' ops; nothing where the
program names no stages."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_round(ctx, "step4_filter")
