"""Share of the traced campaign stretch in which the device ran no
operation, in percent (mean over the devices used)."""


def read(run):
    t = run["trace"]
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * t.idle_share
