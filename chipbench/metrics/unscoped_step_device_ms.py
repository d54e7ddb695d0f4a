"""Device time of the train step that none of its four scopes (forward,
backward, optimizer, dmd_record) covers, per step of the window: time in
which a train-step operation ran but none under those scopes did
(bench/scopes.py). None where the program carries none of them."""
from bench import scopes


def read(view, record, peak):
    events = scopes.device_ops(view)
    if not events or not record.get("steps"):
        return None
    secs = scopes.unscoped_seconds(events)
    return None if secs is None else 1e3 * secs / record["steps"]
