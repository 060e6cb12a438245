"""Device time of the ``precondition`` scope alone per traced step: the
bucket gather, the preconditioner (the ``eva_fused`` launches where the
fused path runs, the inline chain otherwise) and the scatter
(``bench/scopes.py``)."""
from bench import scopes


def read(view):
    return scopes.ms_per_step(view, ('precondition',))
