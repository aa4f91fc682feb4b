"""``setup_cache_misses``: XLA backend compiles in the process, counted: 0 in
a warm run, so a run taken for warm that was not says so.

A process total, not a window delta: ``process_totals`` says why.
"""

from benchmarks.layer_metrics import process_totals


def read(facts):
    return process_totals.total("paddle_compile_events_total",
                                stage="compile")
