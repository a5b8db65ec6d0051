"""NA kernels' device time per live slot, per layer and step, in
microseconds: the summed device time of ``seg_gat_agg_multigraph`` and
``seg_gat_agg_multigraph_bwd`` over the window, over the steps, the NA
launches a step makes (one forward and one backward per layer) and the
plan's live slots (``na_slots`` of ``run_training``'s meta).  Dead slots
cost time but are not counted; nothing where the run records no slots."""
import trace_reduce

KERNELS = ("seg_gat_agg_multigraph",)


def read(trace, rec):
    secs = trace_reduce.kernel_seconds(trace, KERNELS)
    slots = rec.get("na_slots")
    if not secs or not slots:
        return None
    return 1e6 * secs / (rec["steps"] * rec["na_layers"] * sum(slots["live"]))
