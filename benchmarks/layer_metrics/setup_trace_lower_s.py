"""``setup_trace_lower_s``: tracing and lowering over all roles of the
compile ledger, in seconds: what a warm run pays again in full.

A process total, not a window delta: ``process_totals`` says why.
"""

from benchmarks.layer_metrics import process_totals


def read(facts):
    return process_totals.total("paddle_compile_seconds_total",
                                stage=("trace", "lower"))
