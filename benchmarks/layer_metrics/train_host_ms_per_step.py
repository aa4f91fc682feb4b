"""``train_host_ms_per_step``: the host's milliseconds in a steady
``Executor.run`` of the training step (prepare, the enqueueing call,
writeback), over the steady runs.

Process totals (``process_totals``): the training driver keeps no window
deltas of the program's counters. A compiled step's first run is booked
whole under ``phase=first_call`` and counted by
``paddle_executor_first_calls_total``, so neither side of the ratio holds
it; ``phase=fetch``, the wait for a loss, stays apart.
"""

from benchmarks.layer_metrics import process_totals


def read(facts):
    ms = process_totals.total("paddle_executor_host_ms_total", role="train",
                              phase=("prepare", "call", "writeback"))
    runs = process_totals.total("paddle_executor_runs_total", role="train")
    first = process_totals.total("paddle_executor_first_calls_total",
                                 role="train")
    if ms is None or runs is None or first is None or runs <= first:
        return None
    return ms / (runs - first)
