#!/usr/bin/env python3
"""``sizing.py serve`` for a configuration whose session has more than one
kind of layer cache: the same compile of the executor's own steps for a
described v5e, with the table feed of every kind (``sizing.py::size_serve``
feeds the first kind's alone), and the startup program that makes the
weights beside them.

    JAX_PLATFORMS=cpu python benchmarks/sweeps/sizing_kinds.py \
        --config trinity-mini-l5 --buckets 1024,2048,4096

Run by hand; its output is copied into the configuration file's ``sizing``.
Nothing runs on a device. The driver never runs it.
"""

import argparse
import json
import sys

from sizing import _ShapeScope, _compile, _described_devices, _steer_like_tpu

import numpy as np


class _SeededShapeScope(_ShapeScope):
    """A shape scope a startup program can be prepared against: the one
    variable the executor sets before a program runs, the RNG state, is
    kept as its shape."""

    def set_var(self, name, value):
        self._vars[name] = (np.shape(value), value.dtype)


def size_serve(cfg, buckets, startup_too=True):
    import jax
    import paddle_tpu as ptpu
    from benchmarks import architectures
    from benchmarks.harness import lm
    arch = architectures.load(cfg)
    geometry = cfg["deployment"]["serving"]
    one_chip = jax.sharding.SingleDeviceSharding(_described_devices()[0])
    out = {"what": "serve", "config": cfg["name"], "geometry": geometry,
           "programs": {}}
    with lm.flags(generation_paged_kv=True,
                  generation_kv_dtype=geometry["kv_dtype"], **cfg["flags"]):
        with ptpu.unique_name.guard():
            startup = arch.serve_startup(cfg, 0)
        spec = arch.serve_spec(cfg, geometry, buckets)
        scope = _ShapeScope([startup], more=spec.cache_vars)
        exe = ptpu.Executor()
        S, MB = spec.slots, spec.max_blocks
        kinds = spec.cache_kinds or ()
        dfeed = {"gen.dtok": np.zeros((S, 1), "int64"),
                 "gen.dpos": np.zeros((S,), "int32")}
        dfeed.update({k.decode_table: np.zeros((S, MB), "int32")
                      for k in kinds})
        fetches = [spec.decode_fetch] + \
            ([spec.stats_fetch] if spec.stats_fetch else [])
        mem, hlo = _compile(exe, spec.decode_program, dfeed, fetches, scope,
                            one_chip)
        mem["tpu_custom_calls"] = hlo.count("tpu_custom_call")
        out["programs"]["decode"] = mem
        out["hlo_decode"] = hlo
        for P in buckets:
            pfeed = {"gen.ptok": np.zeros((1, P), "int64"),
                     "gen.plen": np.ones((1,), "int32"),
                     "gen.ppos": np.zeros((1,), "int32"),
                     "gen.phist": np.zeros((1,), "int32"),
                     "gen.ppix": np.zeros((P,), "int32")}
            pfeed.update({k.prefill_table: np.zeros((MB,), "int32")
                          for k in kinds})
            mem, _ = _compile(exe, spec.prefill_programs[P], pfeed,
                              [spec.prefill_fetch], scope, one_chip)
            out["programs"]["prefill_%d" % P] = mem
        if startup_too:
            # the weights are this program's outputs; what it needs beside
            # them is the room the initialisers take
            mem, _ = _compile(exe, startup, {}, [], _SeededShapeScope([]),
                              one_chip)
            out["programs"]["startup"] = mem
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--buckets", default="1024,2048,4096")
    ap.add_argument("--hlo", default="", help="write the decode HLO here")
    args = ap.parse_args(argv)
    from benchmarks.harness import lm
    cfg = lm.load_config(args.config)
    _steer_like_tpu()
    rec = size_serve(cfg, [int(b) for b in args.buckets.split(",")])
    hlo = rec.pop("hlo_decode")
    if args.hlo:
        with open(args.hlo, "w") as f:
            f.write(hlo)
    print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
