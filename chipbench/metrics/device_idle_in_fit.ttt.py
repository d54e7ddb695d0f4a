"""Share of the traced window in which the device is idle while the host
is inside ``Trainer.fit`` (any ``repro.fit.*`` span), in the time-to-target
cells: the part of ``device_idle_share.ttt`` that the training loop, not
the driver's own work between trainings, leaves."""
from bench import scopes


def read(view, record, peak):
    fit = scopes.fit_spans(view)
    if not fit or not view.devices or view.window_s <= 0:
        return None
    return 100.0 * scopes.idle_within(view, fit) / view.window_s
