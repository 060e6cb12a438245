"""Device time of the optimizer's part of the step per traced step: the
statistics' finalization (``capture``), the update with every scope nested
in it (``optimizer``: ``kv``, ``precondition``, ``kl_clip``) and the
parameter update (``apply``) (``bench/scopes.py``)."""
from bench import scopes


def read(view):
    return scopes.ms_per_step(view, ('capture', 'optimizer', 'kv',
                                     'precondition', 'kl_clip', 'apply'))
