"""Share of the traced window in which no operation ran on the device
(averaged over the chips used), in percent."""


def read(trace, rec):
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
