"""What both kinds of driver share: the device check, the program's counters
read as window deltas, host spans on the profiler's clock, the traced
sub-window, and the facts a run hands to the per-layer readers."""

import contextlib
import os
import shutil
import time

import numpy as np

from . import trace_reduce
from .compile_meter import CompileMeter
from .lm import CHECKOUT


class BenchFailure(Exception):
    """The run cannot produce a result; the message says why."""


def check(cond, msg, *args):
    if not cond:
        raise BenchFailure(msg % args)


def devices_for(chips, require_tpu=True):
    """The devices a cell runs on, or a failure: a TPU backend with at least
    the chips the cell asks for. ``require_tpu=False`` is for the CPU
    rehearsal in ``benchmarks/tests`` only; the command line never passes it."""
    import jax
    devs = jax.devices()
    if require_tpu:
        check(devs[0].platform == "tpu",
              "JAX's default backend is %r, not a TPU", devs[0].platform)
    check(len(devs) >= chips, "the cell asks for %d chips, JAX sees %d",
          chips, len(devs))
    return devs[:chips]


def device_stamp(devices):
    """The device as JAX reports it, with the peak on the fullest chip."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(max(peaks))}


def span(name):
    """A host span named ``bench:<name>`` on the profiler's clock. Costs a
    flag check when no trace is being taken."""
    import jax
    return jax.profiler.TraceAnnotation("bench:" + name)


def registry_snapshot():
    """The program's counters and histograms as flat dicts:
    counters ``{name or name{k=v,...}: value}``, histograms
    ``{name: (count, sum)}``."""
    from paddle_tpu.observability import metrics
    counters, hists = {}, {}
    for name, kind, _, _, children in metrics.REGISTRY.snapshot():
        for labels, payload in children:
            key = name if not labels else "%s{%s}" % (
                name, ",".join("%s=%s" % kv for kv in sorted(labels.items())))
            if kind == "histogram":
                hists[key] = (payload[1], payload[2])
            elif kind == "counter":
                counters[key] = float(payload)
    return counters, hists


def registry_delta(after, before):
    c1, h1 = after
    c0, h0 = before
    counters = {k: v - c0.get(k, 0.0) for k, v in c1.items()}
    hists = {k: (n - h0.get(k, (0, 0.0))[0], s - h0.get(k, (0, 0.0))[1])
             for k, (n, s) in h1.items()}
    return counters, hists


def kernel_paths_since(before):
    """``{kernel: {path: traced call sites}}`` since a ``counts()`` reading."""
    from paddle_tpu.ops import kernel_path
    out = {}
    for kernel, paths in kernel_path.counts().items():
        delta = {p: n - before.get(kernel, {}).get(p, 0)
                 for p, n in paths.items()}
        delta = {p: n for p, n in delta.items() if n}
        if delta:
            out[kernel] = delta
    return out


def check_kernel_compiled(kernel, paths, on_tpu):
    """The main-path kernel ran as itself: on a TPU every traced call site
    was compiled by Mosaic; off it (the rehearsal) interpreted."""
    got = paths.get(kernel, {})
    want = "compiled" if on_tpu else "interpret"
    check(got.get(want, 0) > 0 and set(got) == {want},
          "kernel %s: traced call sites by path %r, want only %r", kernel,
          got, want)


class Env:
    """One run's surroundings: its clock zero, compile meter, devices and
    output directory."""

    def __init__(self, t_process, workload, chips, trace, require_tpu=True,
                 out_root=None, drain=True):
        from paddle_tpu.core.compile_cache import enable_jax_cache
        self.t_process = t_process
        self.trace = bool(trace)
        # False: the process exits after this run, so a serving cell parks
        # its dispatcher in place of serving out what is in flight
        self.drain = bool(drain)
        self.cache_dir = enable_jax_cache(os.path.join(CHECKOUT, ".jax_cache"))
        self.meter = CompileMeter()
        self.devices = devices_for(chips, require_tpu)
        self.on_tpu = self.devices[0].platform == "tpu"
        self.out_dir = os.path.join(
            out_root or os.path.join(CHECKOUT, ".bench_out"), workload)
        os.makedirs(self.out_dir, exist_ok=True)
        self.trace_dir = os.path.join(self.out_dir, "trace")
        # seconds of set-up by phase, for the notes: what is left of
        # ``setup_s`` is the driver's own (the traffic's draw and lead-in)
        self.phases = {"imports_and_devices":
                       time.perf_counter() - t_process}

    def setup_seconds(self, t_window):
        """Process start to window start."""
        return t_window - self.t_process

    @contextlib.contextmanager
    def phase(self, name):
        """A phase of set-up: a ``bench:`` span, and its seconds kept."""
        t0 = time.perf_counter()
        with span(name):
            yield
        self.phases[name] = time.perf_counter() - t0

    @contextlib.contextmanager
    def traced(self):
        """Profile the block: the device trace plus host spans, Python
        call tracing off (it slows the host it measures)."""
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        try:
            with span("window"):
                yield
        finally:
            jax.profiler.stop_trace()

    def reduce_trace(self):
        """The reduction of the trace ``traced`` wrote."""
        trace = trace_reduce.load_xplane(
            trace_reduce.find_xplane(self.trace_dir))
        return trace_reduce.reduce_trace(
            trace, chips=len(self.devices) if self.on_tpu else None,
            rehearsal=not self.on_tpu)


class Facts:
    """What a run hands to the per-layer readers."""

    def __init__(self, cell, cfg, devices, seconds):
        self.cell = cell
        self.cfg = cfg
        self.chips = len(devices)
        self.device_kind = devices[0].device_kind
        self.seconds = seconds
        self.observed = {}      # client-side and driver-side numbers by name
        self.counters = {}      # window deltas of the program's counters
        self.hists = {}         # window deltas (count, sum) of its histograms
        self.compiles = {}      # compile meter, window delta
        self.trace = None       # trace_reduce.reduce_trace(), traced runs
        self.correct = True
        self.problems = []      # why ``correct`` is false
        self.compared = {}      # {name: {"value", "limit"}}: what decided it
        self.attempted = 0
        self.failed = 0
        self.notes = {}         # printed on an earlier line
        self.samples = {}       # raw samples, kept in the output directory

    def fail(self, msg, *args):
        self.correct = False
        self.problems.append(msg % args)

    def compare(self, name, value, limit, msg, *args):
        """Hold a number to its limit: kept beside the limit for the result
        line, and the run is not correct where it is over it or no number."""
        value = float(value)
        ok = value <= limit     # False for NaN
        self.compared[name] = {
            "value": value if np.isfinite(value) else repr(value),
            "limit": limit}
        if not ok:
            self.fail(msg, *args)
        return ok


def midmean(values):
    """The mean of the middle half: the values sorted, a quarter of them
    (rounded down) dropped at each end. Smooth like a mean, and like a
    median blind to a few values that a stall spoiled. None for no values."""
    values = np.sort(np.asarray(values, np.float64))
    if not values.size:
        return None
    k = values.size // 4
    return float(values[k:values.size - k].mean())


def median_slope(stamps, reach=3):
    """Seconds per step from ``[(steps so far, host time), ...]``: the
    median, over all pairs of stamps at most ``reach`` apart, of the seconds
    between them over the steps between them. A stamp is taken when the host
    wakes, so it can be late. Longer baselines than neighbours average the
    small lateness of every stamp away (2.5 times less spread than the
    median of neighbours, by simulation); no baselines longer than ``reach``,
    so that a stall of the device itself, which shifts every later stamp,
    sits in a fifth of the pairs and not in most of them. A single very late
    stamp is in few pairs either way. None for fewer than two stamps."""
    slopes = [(tb - ta) / (kb - ka)
              for i, (ka, ta) in enumerate(stamps)
              for kb, tb in stamps[i + 1:i + 1 + reach] if kb > ka]
    return float(np.median(slopes)) if slopes else None


def quantile(values, q):
    """np.quantile with linear interpolation; None for no samples."""
    values = np.asarray(values, np.float64)
    return float(np.quantile(values, q)) if values.size else None
