"""Share of the window the train loop spent waiting for its next batch
(the loop's own clock around ``loader.next()``)."""


def read(run):
    m = run.get("train")
    if not m:
        return None
    return 100.0 * m["data_wait_s"] / (m["t_close"] - m["t_open"])
