"""The program's counters as **process totals**, for the metrics of set-up.

Set-up precedes the measured window, so the window deltas a run hands its
readers (``facts.counters``) read zero for it. A correct run compiles
nothing inside the window (``compiles_in_window`` must read 0), so what the
compile ledger holds at the end of the process is set-up's: the readers of
``setup_*`` take it whole from ``paddle_tpu.observability.metrics.REGISTRY``.

A program without the family (before PR 36) has nothing to read: ``total``
returns None and the line leaves the metric out. A family that is there
with no matching child reads 0: a warm run has no ``stage=compile`` child.
"""


def total(family, **labels):
    """Sum over the children of ``family`` whose labels hold every given
    ``key=value`` (a value may be a tuple of allowed values); None where
    the program has no such family."""
    from paddle_tpu.observability import metrics
    fam = metrics.REGISTRY.families().get(family)
    if fam is None:
        return None
    want = {k: v if isinstance(v, tuple) else (v,) for k, v in labels.items()}
    return float(sum(
        child.value for child in fam.children().values()
        if all(child.labels_dict.get(k) in v for k, v in want.items())))
