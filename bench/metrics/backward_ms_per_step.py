"""Device time of the step's backward pass per traced step: the self time
of the ops named under ``transpose(jvp(forward))``, the ``remat``
recompute included (``bench/scopes.py``)."""
from bench import scopes


def read(view):
    return scopes.ms_per_step(view, ('backward',))
