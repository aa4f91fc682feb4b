"""``setup_infer_shape_s``: op shape inference when the programs are built, the
first of a step's three traces, in seconds.

A process total, not a window delta: ``process_totals`` says why.
"""

from benchmarks.layer_metrics import process_totals


def read(facts):
    return process_totals.total("paddle_program_infer_shape_seconds_total")
