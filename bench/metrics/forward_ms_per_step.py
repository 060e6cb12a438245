"""Device time of the step's forward pass per traced step: the self time of
the ops named under ``jvp(forward)`` (``bench/scopes.py``), summed over the
chips used, then averaged."""
from bench import scopes


def read(view):
    return scopes.ms_per_step(view, ('forward',))
