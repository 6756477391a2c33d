"""Share of a step's device operations under ``lm/layers`` and no deeper
scope (``benchmark/step_scopes.py``): what the map over sequences or
slices, the checkpoints and autodiff add between a layer's named parts
(each weight's gradient summed over the map's turns, kept values stacked,
residual adds) and the norms that sit outside ``*/project``."""
from benchmark import step_scopes


def read(run):
    return step_scopes.share(run, "lm/layers")
