"""Model FLOP utilization of training, in percent: the FLOPs one
full-batch step needs (bench/work.py) times the steps of the window,
over the window times the chips times one chip's peak."""


def read(trace, rec):
    pk = rec["peaks"]
    return 100.0 * rec["flops_per_step"] * rec["steps"] / (rec["window_s"] * rec["chips"] * pk["flops_per_s"])
