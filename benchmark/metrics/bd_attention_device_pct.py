"""Share of a step's device operations that carry the program's scope
``bd/attention`` (through ``benchmark/step_scopes.py``): the attention
calls under the block-diffusion mask alone, forward and fused backward,
which says whether the mechanism this cell exists for does most of the
step's work.  None without a trace, or where no operation of a step names
the scope (a program without the objective)."""
from benchmark import step_scopes


def read(run):
    return step_scopes.share(run, "bd/attention")
