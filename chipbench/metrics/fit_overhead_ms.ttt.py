"""Host time of ``Trainer.fit``'s way in and out, per call, in the
time-to-target cells: the ``repro.fit.enter`` and ``repro.fit.exit`` spans
of the window over the number of ``repro.fit.enter`` spans (fit calls)."""
from bench import scopes


def read(view, record, peak):
    enter = scopes.spans(view, "repro.fit.enter")
    if not enter:
        return None
    both = enter + scopes.spans(view, "repro.fit.exit")
    return 1e-6 * sum(e - s for s, e in both) / len(enter)
