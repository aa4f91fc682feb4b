#!/usr/bin/env python3
"""Census of the layout work in a served configuration's compiled program:
every stand-alone ``copy`` / ``convert`` / ``transpose`` of a megabyte or
more, with where its operand comes from.

The program is the executor's own step (the decode program or one prefill
bucket of the configuration's deployment geometry, cut to ``--layers``
layers: every layer of a kind compiles alike), compiled for a *described*
v5e with the chip's own compiler, as ``tests/test_tpu_compile.py`` and
``benchmarks/sweeps/sizing_kinds.py`` do. Nothing runs on a device and no
cell runs this tool.

    JAX_PLATFORMS=cpu python tools/hlo_census.py --config evabyte-6.5b-l8 \
        --layers 2 --program decode
    JAX_PLATFORMS=cpu python tools/hlo_census.py --config evabyte-6.5b-l8 \
        --layers 2 --program 8192 --hlo /root/scratch/prefill.hlo

Instructions inside a fusion are not passes of their own and are left
out; an instruction in the entry computation or in a loop's body is a
pass over HBM that no count of the model's bytes asks for. An operand is
traced back through bitcasts, reshapes, tuple elements and other copies to
a **parameter** of the step (a weight: ``state_ro``), a **pool** (a
donated ``state_rw`` variable: a layer cache) or a feed; anything else is
an **activation**, named by the instruction and the Program op
(``op_name``) that made it.

One JSON line: ``{"config", "program", "layers", "memory", "rows": [{"op",
"shape", "bytes", "from", "source", "op_name", "count"}], "rotary_fenced"}``
(equal rows are merged and counted), then a table on stderr.
"""

import argparse
import collections
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

OPCODES = ("copy", "convert", "transpose")
_ITEM = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
         "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
         "f64": 8}
# instructions an operand is looked through on its way back to its source
# (XLA's own prefetch of a weight is slices gathered by a ``ConcatBitcast``
# custom call)
_THROUGH = ("bitcast", "reshape", "get-tuple-element", "copy",
            "copy-start", "copy-done", "slice-start", "slice-done",
            "optimization-barrier")
_INSTR = re.compile(
    r"^\s*(?:ROOT )?%(?P<name>[\w.\-]+) = (?P<type>\(.*?\)|[a-z0-9]+\[[^\]]*\]"
    r"(?:\{[^}]*\})?) (?P<op>[a-z\-]+)\((?P<args>.*)$")
_SHAPE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
# an instruction's first operand, with or without its type before it
_OPERAND = re.compile(r"\s*(?:[a-z0-9]+\[[^\]]*\](?:\{[^}]*\})? )?"
                      r"%([\w.\-]+)")
_LAYER = re.compile(r"(?<![a-z0-9])l\d+(?![a-z0-9])")


def _bytes(shape):
    """Bytes of ``dtype[dims]`` (the first array of a tuple type)."""
    m = _SHAPE.search(shape)
    if not m or m.group(1) not in _ITEM:
        return 0
    n = 1
    for d in filter(None, m.group(2).split(",")):
        n *= int(d)
    return n * _ITEM[m.group(1)]


def _computations(hlo):
    """{computation: [line]} of an HLO module's text, and the names of the
    computations that are a fusion's body."""
    comps, fused, name = collections.OrderedDict(), set(), None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
            if " fusion(" in line:
                fused.update(re.findall(r"calls=%([\w.\-]+)", line))
    return comps, fused


def _source(name, table, pools):
    """Where instruction ``name``'s value comes from: ('parameter' | 'pool'
    | 'feed' | 'activation', what)."""
    seen = set()
    while name in table and name not in seen:
        seen.add(name)
        op, args, meta = table[name]
        if op == "parameter":
            var = re.sub(r"^(state_r[ow]|feeds)__|__(\.\d+)?$", "", name)
            if name.startswith("state_ro"):
                return "parameter", var
            if name.startswith("state_rw"):
                return ("pool" if var in pools else "state"), var
            return "feed", var
        if op not in _THROUGH and "ConcatBitcast" not in args:
            return "activation", ("%s %s" % (op, meta)).strip()
        first = _OPERAND.match(args)
        if not first:
            break
        name = first.group(1)
    return "activation", name


def census(hlo, pools=(), min_bytes=1 << 20):
    """Rows of every stand-alone ``copy`` / ``convert`` / ``transpose`` of
    at least ``min_bytes`` in ``hlo`` (a compiled module's text), equal
    rows merged: [{op, shape, bytes, from, source, op_name, count}],
    largest first. ``pools``: the names of the session's cache variables."""
    pools = {re.sub(r"\W", "_", p) for p in pools}
    comps, fused = _computations(hlo)
    rows = collections.Counter()
    for comp, lines in comps.items():
        if comp in fused:
            continue
        table, found = {}, []
        for line in lines:
            m = _INSTR.match(line)
            if not m:
                continue
            meta = re.search(r'op_name="([^"]*)"', line)
            meta = meta.group(1) if meta else ""
            table[m.group("name")] = (m.group("op"), m.group("args"), meta)
            if m.group("op") in OPCODES and \
                    _bytes(m.group("type")) >= min_bytes:
                found.append((m.group("name"), m.group("op"),
                              re.sub(r"\{[^}]*\}", "", m.group("type")),
                              m.group("args"), meta))
        for name, op, shape, args, meta in found:
            first = _OPERAND.match(args)
            kind, what = _source(first.group(1), table, pools) if first \
                else ("activation", "?")
            # a weight's own name says the layer: merge layers' rows
            rows[(op, shape, _bytes(shape), kind, _LAYER.sub("l*", what),
                  _LAYER.sub("l*", meta))] += 1
    out = [dict(zip(("op", "shape", "bytes", "from", "source", "op_name"),
                    k), count=n) for k, n in rows.items()]
    return sorted(out, key=lambda r: (-r["bytes"] * r["count"], r["shape"]))


def _layer_keys(cfg, layers):
    """``cfg`` cut to ``layers`` layers: the layer count under the key the
    configuration has it, and ``layer_types`` as the kinds it holds in
    their first order, then the list from its start."""
    for key in ("num_hidden_layers", "num_layers", "n_layer"):
        if key in cfg:
            cfg[key] = layers
    kinds = cfg.get("layer_types")
    if kinds:
        first = list(dict.fromkeys(kinds))
        cfg["layer_types"] = (first + kinds * layers)[:max(layers, 1)]
    if "num_dense_layers" in cfg:
        cfg["num_dense_layers"] = min(cfg["num_dense_layers"], layers - 1)


def _fenced():
    """Projections fenced at a trace so far in this process
    (``paddle_rotary_fenced_total`` over its ops; 0 where the program has
    no such counter)."""
    from paddle_tpu.observability import metrics
    family = metrics.REGISTRY.families().get("paddle_rotary_fenced_total")
    return int(sum(c.value for c in family.children().values())) \
        if family else 0


def compile_program(config, layers, program, sharding=None):
    """(memory, HLO text, pools' names, projections fenced at the trace) of
    ``config``'s decode program (``program`` ``"decode"``) or prefill bucket
    (an int), cut to ``layers`` layers (0: as the file has it), compiled
    for a described v5e chip (``sharding``: a described chip's
    ``SingleDeviceSharding``; absent, one is described here)."""
    import numpy as np
    import jax
    import paddle_tpu as ptpu
    from benchmarks import architectures
    from benchmarks.harness import lm
    from benchmarks.sweeps import sizing
    cfg = lm.load_config(config)
    if layers:
        _layer_keys(cfg, layers)
    arch = architectures.load(cfg)
    geometry = cfg["deployment"]["serving"]
    if sharding is None:
        sharding = jax.sharding.SingleDeviceSharding(
            sizing._described_devices()[0])
    bucket = None if program == "decode" else int(program)
    with lm.flags(generation_kv_dtype=geometry["kv_dtype"],
                  matmul_precision="BF16_BF16_F32", **cfg["flags"]):
        with ptpu.unique_name.guard():
            startup = arch.serve_startup(cfg, 0)
        spec = arch.serve_spec(cfg, geometry, (bucket or 2048,))
        scope = sizing._ShapeScope([startup], more=spec.cache_vars)
        kinds = spec.cache_kinds or ()
        if bucket is None:
            prog = spec.decode_program
            names = list(spec.decode_feeds[:3]) + \
                [k.decode_table for k in kinds]
            fetch = [spec.decode_fetch] + \
                ([spec.stats_fetch] if spec.stats_fetch else [])
        else:
            prog = spec.prefill_programs[bucket]
            names = list(spec.prefill_feeds[:6]) + \
                [k.prefill_table for k in kinds]
            fetch = [spec.prefill_fetch]
        block = prog.global_block()
        feed = {n: np.ones(block.var(n).shape, "int32")
                for n in dict.fromkeys(names)}
        before = _fenced()
        mem, hlo = sizing._compile(ptpu.Executor(), prog, feed, fetch, scope,
                                   sharding)
        fenced = _fenced() - before
    return mem, hlo, [name for name, _, _ in spec.cache_vars], fenced


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--layers", type=int, default=2,
                    help="layers to keep (0: the file's own)")
    ap.add_argument("--program", default="decode",
                    help="'decode' or a prefill bucket's length")
    ap.add_argument("--min-bytes", type=int, default=1 << 20)
    ap.add_argument("--hlo", default="", help="write the HLO text here")
    args = ap.parse_args(argv)
    from benchmarks.sweeps import sizing
    sizing._steer_like_tpu()
    mem, hlo, pools, fenced = compile_program(args.config, args.layers,
                                              args.program)
    if args.hlo:
        with open(args.hlo, "w") as f:
            f.write(hlo)
    rows = census(hlo, pools, args.min_bytes)
    print(json.dumps({"config": args.config, "program": args.program,
                      "layers": args.layers, "memory": mem, "rows": rows,
                      "rotary_fenced": fenced}), flush=True)
    for r in rows:
        print("%2d x %-9s -> %-28s %8.1f MB  from %-10s %s  [%s]" % (
            r["count"], r["op"], r["shape"], r["bytes"] / 1e6, r["from"],
            r["source"], r["op_name"]), file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
