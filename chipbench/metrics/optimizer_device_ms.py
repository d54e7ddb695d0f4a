"""Device time of the train step's optimizer update (and gradient sync
where it is on), per step of the window: the operations under the
program's ``optimizer`` scope (bench/scopes.py)."""
from bench import scopes


def read(view, record, peak):
    return scopes.per_step_ms(view, record, scopes.optimizer)
