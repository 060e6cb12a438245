"""Mean host time per traced step of the training loop's ``train.host``
span: the loop's work after the loss read-back (watchdog, scalar step
fields, records, checkpoint check)."""
from bench import scopes


def read(view):
    return scopes.mean_span_ms(view, 'train.host')
