"""Share of the traced window in which no operation ran on the device,
in the time-to-target cells (averaged over the chips used)."""


def read(view, record, peak):
    if view.window_s <= 0 or not view.devices:
        return None
    return 100.0 * (1.0 - view.busy_s() / view.window_s)
