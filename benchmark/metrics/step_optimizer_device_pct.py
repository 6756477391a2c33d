"""Share of a step's device operations in the optimizer's update and, where
the numerics guard is on, its health, clip and select: the program's
scopes ``step/optimizer`` and ``step/grad_health``
(``benchmark/step_scopes.py``)."""
from benchmark import step_scopes


def read(run):
    return step_scopes.share(run, "step/optimizer", "step/grad_health")
