"""The serve step's share of the chip's roofline, in percent: the least
time of the work the step needs (reference ``decode_cost``: the weights
once, each live row's cache or state, the matmul FLOPs), over the step
program's mean device time in the trace."""
from benchmarks.chip.peaks import least_time_s


def read(run):
    rounds = run.layer.get("traced_positions")
    step_s = run.trace.get("module_s")
    if not rounds or not step_s:
        return None
    least = [least_time_s(*run.ref.decode_cost(run.dims, pos),
                          run.device_kind) for pos in rounds]
    return 100.0 * sum(least) / len(least) / step_s
