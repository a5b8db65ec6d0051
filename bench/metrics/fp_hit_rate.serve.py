"""Share of FP cache block lookups in the window that hit, in percent:
the engine's cache hit and miss counters, read before and after the window."""


def read(trace, rec):
    return 100.0 * rec["fp_hit_rate"]
