"""Share of a step's device operations in the final norm, the head's
product and the (chunked) loss, forward and backward: the program's scope
``lm/head_loss`` (``benchmark/step_scopes.py``)."""
from benchmark import step_scopes


def read(run):
    return step_scopes.share(run, "lm/head_loss")
