"""Device time of the train step's attention core, forward and backward,
per step of the window: the operations of the train-step program under
the program's ``attention`` scope, whichever core (kernel or jnp) runs
there (bench/scopes.py reads the scopes). None for a program without that
scope."""
from bench import scopes


def attention(op: scopes.Op) -> bool:
    return scopes.in_train_step(op) and "attention" in op.scope


def read(view, record, peak):
    return scopes.per_step_ms(view, record, attention)
