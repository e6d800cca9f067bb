"""The MoE serve step's share of the chip's roofline, in percent, read as
``serve.step_mfu`` reads it (that file's ``read``): the least time of
the work the step needs (``reference/moonlight.py:decode_cost``), over
the step program's mean device time in the trace."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_metric_serve_step_mfu",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "serve.step_mfu.py"))
_serve = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_serve)


def read(run):
    return _serve.read(run)
