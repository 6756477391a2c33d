"""Share of a step's device operations in the linear layers' token mixing
itself: the causal convolution with its silu (the program's scope
``gdn/conv``) and the recurrence with what feeds it (``gdn/recurrence``:
l2norm, the decay, beta, and everything under ``gdn_scan``), forward and
backward (``benchmark/step_scopes.py``).  The layer's projections, its
gated norm and ``W_out`` are ``gdn/project``'s and not counted here."""
from benchmark import step_scopes


def read(run):
    return step_scopes.share(run, "gdn/conv", "gdn/recurrence")
