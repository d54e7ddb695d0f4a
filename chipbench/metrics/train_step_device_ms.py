"""Device time of the train step program, per step of the window."""


def is_train_step(name: str) -> bool:
    return "train_step" in name


def read(view, record, peak):
    secs, n = view.module_time(is_train_step)
    if n == 0 or not record.get("steps"):
        return None
    return 1e3 * secs / record["steps"]
