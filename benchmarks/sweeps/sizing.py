#!/usr/bin/env python3
"""Sizes a configuration for the v5e without the chip: compiles the exact
step ``Executor.run`` would execute, for a *described* ``v5e:2x2`` topology,
and prints the compiler's ``memory_analysis()`` bytes per device.

    JAX_PLATFORMS=cpu python benchmarks/sweeps/sizing.py train \
        --config cerebras-gpt-1.3b-l10 --set <depth key>=11 --batch 4 --seq 2048
    JAX_PLATFORMS=cpu python benchmarks/sweeps/sizing.py train \
        --config cerebras-gpt-1.3b --mesh data=2,model=2 --batch 8 --seq 2048
    JAX_PLATFORMS=cpu python benchmarks/sweeps/sizing.py serve \
        --config cerebras-gpt-1.3b --buckets 128,2048

Run by hand when a configuration, a geometry or the program's memory use
changes; its output is copied into the configuration file's ``sizing``.
Nothing runs on a device, so it says nothing about results or times. The
driver never runs it.

How it hands the program a chip that is not there: the kernels' one switch
(``ops/kernel_path.interpret_mode``) and the matmul precision are steered
here as a TPU backend would set them, the scope holds shapes in place of
arrays, and a ``DistStrategy`` subclass places shapes on the described mesh.
"""

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np  # noqa: E402


def _described_devices():
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2").devices


class _ShapeScope:
    """A scope that answers with shapes: enough for ``Executor._prepare``
    to assemble the step's state arguments."""

    def __init__(self, programs, more=()):
        """Persistable variables of ``programs``, and ``more``:
        (name, shape, dtype) of variables no program declares with a shape
        of its own (a session's cache)."""
        self._vars = {}
        for prog in programs:
            for name, var in prog.global_block().vars.items():
                if getattr(var, "persistable", False) and var.shape:
                    self._vars[name] = (var.shape, var.dtype)
        self._vars.update({n: (s, d) for n, s, d in more})

    def has_var(self, name):
        return name in self._vars

    def find_var(self, name):
        import jax
        from paddle_tpu.core.framework import convert_dtype
        shape, dtype = self._vars[name]
        return jax.ShapeDtypeStruct(tuple(int(d) for d in shape),
                                    convert_dtype(dtype))

    def set_var(self, name, value):
        raise RuntimeError("sizing scope is read-only (%s)" % name)


def _shape_strategy(arch, cfg, mesh_axes, devices):
    """The cell's DistStrategy, placing shapes in place of arrays."""
    import jax
    base = arch.strategy(cfg, mesh_axes, devices)

    def shard_feed(name, array):
        return jax.ShapeDtypeStruct(
            np.shape(array), array.dtype,
            sharding=base.feed_sharding(name, np.ndim(array)))

    def shard_state(name, array, dist_rows=None):
        return jax.ShapeDtypeStruct(
            np.shape(array), array.dtype,
            sharding=base.state_sharding(name, np.ndim(array),
                                         np.shape(array), dist_rows))
    base.shard_feed = shard_feed
    base.shard_state = shard_state
    return base


def _compile(exe, program, feed, fetch_list, scope, one_chip):
    """Compile the executor's own jitted step for shapes; returns
    (memory dict, HLO text)."""
    import jax
    entry, state_rw, state_ro, feeds = exe._prepare(
        program, feed, fetch_list, scope, True, count_cache=False)
    if exe.strategy is None:
        def place(tree):
            return {n: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                            sharding=one_chip)
                    for n, a in tree.items()}
        state_rw, state_ro, feeds = place(state_rw), place(state_ro), \
            place(feeds)
    compiled = entry.fn.lower(state_rw, state_ro, feeds).compile()
    m = compiled.memory_analysis()
    mem = {"argument_bytes": int(m.argument_size_in_bytes),
           "output_bytes": int(m.output_size_in_bytes),
           "alias_bytes": int(m.alias_size_in_bytes),
           "temp_bytes": int(m.temp_size_in_bytes),
           "code_bytes": int(m.generated_code_size_in_bytes)}
    mem["live_bytes"] = (mem["argument_bytes"] + mem["output_bytes"]
                         - mem["alias_bytes"] + mem["temp_bytes"])
    return mem, compiled.as_text()


def _steer_like_tpu():
    import jax
    import paddle_tpu as ptpu
    from jax.experimental.compilation_cache import compilation_cache
    from paddle_tpu.ops import kernel_path
    kernel_path.interpret_mode = lambda: False
    ptpu.config.set_flags(matmul_precision="BF16_BF16_F32")
    # an executable compiled for a described device cannot be read back
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()


def size_train(cfg, mesh_axes, batch, seq):
    import jax
    import paddle_tpu as ptpu
    from benchmarks import architectures
    from benchmarks.harness import lm
    arch = architectures.load(cfg)
    devices = _described_devices()
    one_chip = jax.sharding.SingleDeviceSharding(devices[0])
    strategy = _shape_strategy(arch, cfg, mesh_axes, devices) \
        if mesh_axes else None
    traffic = {"batch": batch, "seq_len": seq, "learning_rate": 1e-4}
    with lm.flags(**cfg["flags"]), ptpu.unique_name.guard():
        main, startup, loss = arch.train_program(cfg, traffic, 0)
        exe = ptpu.Executor(strategy=strategy)
        step = arch.train_feed(np.random.RandomState(0), cfg, traffic)
        mem, hlo = _compile(exe, main, step["feed"], [loss],
                            _ShapeScope([main, startup]), one_chip)
    n_params = sum(int(np.prod(p.shape))
                   for p in main.global_block().all_parameters())
    return {"what": "train", "config": cfg["name"],
            "reducible": {k: cfg[k] for k in arch.published(cfg)["reducible"]},
            "mesh": mesh_axes or None, "batch": batch, "seq": seq,
            "units_per_step": step["units_per_step"],
            "n_params": n_params, "per_device": mem,
            "tpu_custom_calls": hlo.count("tpu_custom_call"),
            "all_reduce": hlo.count(" all-reduce("),
            "all_gather": hlo.count(" all-gather("),
            "reduce_scatter": hlo.count(" reduce-scatter(")}


def size_serve(cfg, buckets):
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
        dfeed = {"gen.dtok": np.zeros((S, 1), "int64"),
                 "gen.dpos": np.zeros((S,), "int32"),
                 "gen.dtab": np.zeros((S, MB), "int32")}
        mem, hlo = _compile(exe, spec.decode_program, dfeed,
                            [spec.decode_fetch], scope, one_chip)
        mem["tpu_custom_calls"] = hlo.count("tpu_custom_call")
        out["programs"]["decode"] = mem
        for P in buckets:
            pfeed = {"gen.ptok": np.zeros((1, P), "int64"),
                     "gen.plen": np.ones((1,), "int32"),
                     "gen.ppos": np.zeros((1,), "int32"),
                     "gen.phist": np.zeros((1,), "int32"),
                     "gen.ppix": np.zeros((P,), "int32"),
                     "gen.ptab": np.zeros((MB,), "int32")}
            mem, _ = _compile(exe, spec.prefill_programs[P], pfeed,
                              [spec.prefill_fetch], scope, one_chip)
            out["programs"]["prefill_%d" % P] = mem
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("what", choices=("train", "serve"))
    ap.add_argument("--config", required=True)
    ap.add_argument("--set", action="append", default=[], metavar="KEY=INT",
                    help="override a key the configuration may reduce")
    ap.add_argument("--mesh", default="", help="e.g. data=2,model=2")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--buckets", default="128")
    args = ap.parse_args(argv)
    from benchmarks import architectures
    from benchmarks.harness import lm
    cfg = lm.load_config(args.config)
    reducible = architectures.load(cfg).published(cfg)["reducible"]
    for key, value in (kv.split("=") for kv in args.set):
        if key not in reducible:
            ap.error("%s may reduce %s, not %r" % (
                cfg["architecture"], sorted(reducible), key))
        cfg[key] = int(value)
    _steer_like_tpu()
    if args.what == "train":
        mesh = {k: int(v) for k, v in
                (kv.split("=") for kv in args.mesh.split(",") if kv)}
        rec = size_train(cfg, mesh, args.batch, args.seq)
    else:
        rec = size_serve(cfg, [int(b) for b in args.buckets.split(",")])
    print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
