"""Share of a step's device operations that recompute a checkpoint's
forward in the backward pass (``rematted_computation`` in the operation's
``tf_op``), whatever their scope (``benchmark/step_scopes.py``); 0 for a
step without checkpoints."""
from benchmark import step_scopes


def read(run):
    return step_scopes.recomputed_share(run)
