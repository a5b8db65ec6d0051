"""Model FLOP utilization of the serving engine while it steps, in
percent: the HAN-layer FLOPs of the metapath work that the engine steps
started in the window ran (bench/work.py; a request's projection counted
once, with its first metapath), over the summed wall time of those
``HGNNEngine.step()`` calls times one chip's peak."""


def read(trace, rec):
    if rec["engine_step_s"] <= 0.0:
        return None
    return 100.0 * rec["step_flops"] / (rec["engine_step_s"] * rec["peaks"]["flops_per_s"])
