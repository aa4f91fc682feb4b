"""What the drivers share that is not about a model's shape: the benchmark's
data files by name, the program's flags for a block, the logits behind a
greedy token. A model's shape lives in ``benchmarks/architectures/``.
"""

import contextlib
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)


def load_json(*parts):
    """A data file of the benchmark, by its path under ``benchmarks/``."""
    path = os.path.join(BENCH_DIR, *parts)
    with open(path) as f:
        return json.load(f)


def load_config(name):
    cfg = load_json("configs", name + ".json")
    if cfg["name"] != name:
        raise ValueError("configs/%s.json names itself %r" % (name, cfg["name"]))
    return cfg


@contextlib.contextmanager
def flags(**kw):
    """Set paddle_tpu config flags for a block and restore them after."""
    import paddle_tpu as ptpu
    prev = {k: ptpu.config.get_flag(k) for k in kw}
    ptpu.config.set_flags(**kw)
    try:
        yield
    finally:
        ptpu.config.set_flags(**prev)


def logits_var(program, fetch_name):
    """Name of the logits row the program's greedy epilogue (argmax) reads."""
    for op in program.global_block().ops:
        if op.type == "arg_max" and fetch_name in sum(op.outputs.values(), []):
            return op.inputs["X"][0]
    raise LookupError("no argmax produces %r" % fetch_name)
