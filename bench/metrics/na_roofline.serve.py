"""NA kernel's share of its roofline in serving, in percent.

The least time of the NA forward work of the engine steps that started
in the traced window (bench/work.py, from real edges; the larger of
FLOPs over peak and bytes over bandwidth), over the summed device time
of the ``seg_gat_agg_multigraph`` kernel in that window.
"""
import trace_reduce

KERNELS = ("seg_gat_agg_multigraph",)


def read(trace, rec):
    secs = trace_reduce.kernel_seconds(trace, KERNELS)
    if not secs:
        return None
    pk = rec["peaks"]
    flops, nbytes = rec["na_fwd_in_window"]
    return 100.0 * max(flops / pk["flops_per_s"], nbytes / pk["bytes_per_s"]) / secs
