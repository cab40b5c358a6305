"""1 - the union of device-op intervals over the traced slice, averaged
over the chips used."""


def read(run):
    red = run["reduced"]
    if not red["window_s"]:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
