"""``setup_cache_read_s``: retrievals from JAX's persistent cache over all
roles of the compile ledger, in seconds: a warm run's compile-side share.

A process total, not a window delta: ``process_totals`` says why.
"""

from benchmarks.layer_metrics import process_totals


def read(facts):
    return process_totals.total("paddle_compile_seconds_total",
                                stage="cache_read")
