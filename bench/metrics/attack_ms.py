"""Device milliseconds per round of the simulated Byzantine and fault
behaviour (update and data attacks, ``faults.corrupt_updates``): the
summed device time of the ops in the program's ``attack`` stage inside
the traced window, over the rounds that ran (``bench/scopes.py``).  0
where XLA fused all of the stage's work into other stages' ops; nothing
where the program names no stages."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_round(ctx, "attack")
