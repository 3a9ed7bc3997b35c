"""Share of the measured window in which no operation ran on the device.

1 - busy / window, where busy is the union of the device's op intervals in
the window (``trace_reduce``).
"""


def read(run, trace):
    if not trace or not trace["window_s"] or not trace["devices"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
