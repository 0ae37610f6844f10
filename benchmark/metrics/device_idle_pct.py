"""Device: the share of the traced window in which no op ran on the
chip, 100 x (1 - busy union / window), averaged over the chips."""


def read(view):
    busy = view.device_seconds()
    if busy <= 0 or view.window_s <= 0:
        return None
    return 100.0 * (1.0 - busy / view.window_s)
