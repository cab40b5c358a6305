"""Tokens of the steps that completed inside the window (barrier on the
UPDATED state at the window's two ends), over the window."""


def read(run):
    if run["kind"] != "train":
        return None
    m = run["train"]
    return m["steps"] * m["tokens_per_step"] / (m["t_close"] - m["t_open"])
