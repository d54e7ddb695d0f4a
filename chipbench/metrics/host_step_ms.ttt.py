"""Host time of one ``Trainer.fit`` loop iteration in the time-to-target
cells: the mean duration of the program's ``repro.fit.step`` spans in the
window (batch, step dispatch, callback)."""
from bench import scopes


def read(view, record, peak):
    steps = scopes.spans(view, "repro.fit.step")
    if not steps:
        return None
    return 1e-6 * sum(e - s for s, e in steps) / len(steps)
