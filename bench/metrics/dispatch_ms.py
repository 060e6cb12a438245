"""Mean host time per traced step of the training loop's ``train.dispatch``
span: the jitted step's call up to its return (argument flattening,
donation, enqueue)."""
from bench import scopes


def read(view):
    return scopes.mean_span_ms(view, 'train.dispatch')
