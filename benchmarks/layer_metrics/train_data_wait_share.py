"""Share of the window the training loop spent getting the next batch and
putting it on the device (host clock around ``next(batches)`` + ``device_put``
in the benchmark's own loop), in percent of the window."""


def read(view):
    c = view["counters"]
    if "data_wait_s" not in c:
        return None
    return 100.0 * c["data_wait_s"] / c["window_s"]
