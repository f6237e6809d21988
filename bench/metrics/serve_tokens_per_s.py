"""Output tokens on the host at the window's end (the step in flight
then counted in proportion to its time inside the window) over the
window's length."""


def read(rec):
    if rec["kind"] != "serve" or rec["tokens_in_window"] is None:
        return None
    return rec["tokens_in_window"] / rec["window_s"]
