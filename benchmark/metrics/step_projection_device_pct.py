"""Share of a step's device operations in the attention blocks' projections
(q, k, v, out, with what each model puts beside them: latent norms,
per-head norms, rotary), forward and backward: the program's scopes
``mla/project``, ``gqa/project`` and ``mha/project``
(``benchmark/step_scopes.py``)."""
from benchmark import step_scopes


def read(run):
    return step_scopes.share(run, "mla/project", "gqa/project",
                             "mha/project")
