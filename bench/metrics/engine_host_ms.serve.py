"""Median host wall time of the ``HGNNEngine.step()`` calls that started
in the window, in milliseconds (the harness's own clock around each call)."""
import common


def read(trace, rec):
    return common.median(rec["engine_step_ms"])
