"""Share of the serving engine's own working time in which no operation
ran on the device, in percent: of the time that the harness's
``bench.engine_step`` spans (one ``HGNNEngine.step()`` call each) cover
in the traced window, the part in which the chip was idle.  It falls as
the host stops holding the chip back; nothing where no step ran."""

SPAN = "bench.engine_step"


def read(trace, rec):
    span = trace["spans"].get(SPAN)
    if not span or span["s"] <= 0.0:
        return None
    return 100.0 * (1.0 - span["busy_s"] / span["s"])
