"""The readers of per-layer metrics. A metric is a data file,
``benchmarks/layer_metrics/<name>.json``, whose ``reader`` names one of the
kinds below with its arguments; a metric that needs a new source brings a
module ``benchmarks/layer_metrics/<name>.py`` with ``read(facts)`` instead.

A reader that finds nothing to read returns None, and the harness leaves
that metric out of the line.
"""

import importlib
import os

from . import flops, lm, peaks
from .. import architectures


def hist_mean(facts, histogram):
    """Mean of one of the program's histograms over the window (sum over
    count of the window's delta). The histograms are bucketed, so their
    quantiles are coarse; sums and counts are exact."""
    count, total = facts.hists.get(histogram, (0, 0.0))
    return total / count if count else None


def counter_ratio(facts, numerator, denominator, minus=()):
    """(sum of ``numerator`` counters - sum of ``minus``) / ``denominator``,
    all as window deltas. A name matches itself and its labelled children."""
    def total(names):
        return sum(v for k, v in facts.counters.items()
                   if any(k == n or k.startswith(n + "{") for n in names))
    den = total([denominator])
    return (total(numerator) - total(minus)) / den if den else None


def observed(facts, key):
    """A number the driver took itself (client-side timestamps, host clock
    between fetched losses, the compile meter)."""
    return facts.observed.get(key)


def trace(facts, key, scale=1.0):
    """A number of the trace reduction; traced runs only."""
    if facts.trace is None or facts.trace.get(key) is None:
        return None
    return facts.trace[key] * scale


def mfu(facts):
    """Model FLOP/s utilization of a training run, in percent."""
    rate = facts.observed.get("train_tokens_per_s")
    if rate is None:
        return None
    peak = peaks.peaks_for(facts.device_kind)["bf16_flops"]
    per_token = architectures.load(facts.cfg).train_flops_per_token(
        facts.cfg, facts.observed["seq_len"])
    return 100.0 * flops.mfu(per_token, rate, facts.chips, peak)


KINDS = {"hist_mean": hist_mean, "counter_ratio": counter_ratio,
         "observed": observed, "trace": trace, "mfu": mfu}


def load_metric(name):
    """(definition, read function) of a per-layer metric, by its name."""
    spec = lm.load_json("layer_metrics", name + ".json")
    if spec["name"] != name:
        raise ValueError("layer_metrics/%s.json names itself %r"
                         % (name, spec["name"]))
    if os.path.exists(os.path.join(lm.BENCH_DIR, "layer_metrics", name + ".py")):
        mod = importlib.import_module("benchmarks.layer_metrics." + name)
        return spec, mod.read
    reader = dict(spec["reader"])
    fn = KINDS[reader.pop("kind")]
    return spec, lambda facts: fn(facts, **reader)
