"""``setup_compile_s``: XLA backend compiles over all roles of the compile
ledger, in seconds: 0 in a warm run.

A process total, not a window delta: ``process_totals`` says why.
"""

from benchmarks.layer_metrics import process_totals


def read(facts):
    return process_totals.total("paddle_compile_seconds_total",
                                stage="compile")
