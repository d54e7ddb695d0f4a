"""Device time of the train step's forward pass, per step of the window:
the operations under the program's ``forward`` scope, which the gradient
names ``jvp(forward)`` (bench/scopes.py)."""
from bench import scopes


def read(view, record, peak):
    return scopes.per_step_ms(view, record, scopes.forward)
