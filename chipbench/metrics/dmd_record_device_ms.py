"""Device time of the train step's DMD record, per recorded step of the
window: the operations under the program's ``dmd_record`` scope, the
per-group conditionals that write the snapshot and the streaming Gram
row (bench/scopes.py)."""
from bench import scopes


def read(view, record, peak):
    return scopes.per_step_ms(view, record, scopes.dmd_record,
                              per="record_steps")
