"""NA kernels' share of their roofline in training, in percent.

The least time of the window's NA work, forward and backward, each the
larger of FLOPs over the peak FLOP rate and bytes over the peak
bandwidth (bench/work.py counts both from real edges; at these sizes the
bytes bound), over the summed device time of the
``seg_gat_agg_multigraph`` and ``seg_gat_agg_multigraph_bwd`` kernels.
"""
import trace_reduce

KERNELS = ("seg_gat_agg_multigraph",)


def read(trace, rec):
    secs = trace_reduce.kernel_seconds(trace, KERNELS)
    if not secs:
        return None
    pk = rec["peaks"]
    least = sum(max(f / pk["flops_per_s"], b / pk["bytes_per_s"]) for f, b in (rec["na_fwd"], rec["na_bwd"]))
    return 100.0 * least * rec["steps"] / secs
