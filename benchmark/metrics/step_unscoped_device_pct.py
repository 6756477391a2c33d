"""Share of a step's device operations that no scope of the program's
vocabulary names (``benchmark/step_scopes.py``): what keeps the vocabulary
whole.  A model that enters no scope for a new part shows here."""
from benchmark import step_scopes


def read(run):
    found = step_scopes.table(run)
    return None if found is None \
        else found.share(step_scopes.UNSCOPED) or 0.0
