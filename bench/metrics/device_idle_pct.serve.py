"""Share of the traced window in which no operation runs on a device, mean
over the devices (serving cells)."""


def read(run):
    red = run.get("trace")
    if red is None or run.get("kind") != "serve":
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
