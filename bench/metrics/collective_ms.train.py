"""Device time of the collective operations per training step and chip,
in milliseconds; nothing where the trace holds no collective."""


def read(trace, rec):
    if trace["collective_s"] <= 0.0:
        return None
    return 1e3 * trace["collective_s"] / trace["devices"] / rec["steps"]
