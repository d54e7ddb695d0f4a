"""Device time of the train step's backward pass, per step of the window:
the operations under ``transpose(jvp(forward))``, the gradient's transpose
of the program's ``forward`` scope (bench/scopes.py)."""
from bench import scopes


def read(view, record, peak):
    return scopes.per_step_ms(view, record, scopes.backward)
