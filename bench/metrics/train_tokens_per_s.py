"""Tokens of every step dispatched in the window over the window, which
ends when the last step's state is ready."""


def read(rec):
    if rec["kind"] != "train":
        return None
    return rec["tokens"] / rec["window_s"]
