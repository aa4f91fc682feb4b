"""The main path's Pallas kernels, compiled at real widths for a TPU
v5e that is described, not attached (guide on-chip-measurement §2,
rehearsal 3). Nothing runs: a pass means the chip's compiler (Mosaic +
XLA:TPU, installed with libtpu) accepts the kernel at that geometry and
that the program holds a ``tpu_custom_call`` — i.e. neither interpret
mode nor an XLA reference was compiled in its place. Interpret mode
cannot see tiling or VMEM refusals; this file can, at no chip time.

Skipped as a whole where the topology cannot be described (no libtpu).
"""

import functools
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs under /tmp

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops import kernel_path, pallas_attention as pa
from paddle_tpu.ops import pallas_conv_bn, quant_ops

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "tools"))

import hlo_census  # noqa: E402

F32, BF16, I8, I32 = jnp.float32, jnp.bfloat16, jnp.int8, jnp.int32


@pytest.fixture(scope="module")
def described():
    """The four chips of a described ``v5e:2x2``. JAX's persistent
    compile cache is off for the module: an executable compiled for a
    described device is written to it but cannot be read back without
    the chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no libtpu
        pytest.skip("cannot describe a v5e topology here: %r" % (e,))
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(described):
    """SingleDeviceSharding on one described v5e chip."""
    return jax.sharding.SingleDeviceSharding(described[0])


def _compile(chip, fn, *shapes):
    """HLO text of ``fn`` compiled for the described chip under the
    matmul precision the executor traces TPU steps with
    (config.resolve_matmul_precision)."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    with jax.default_matmul_precision("BF16_BF16_F32"):
        return jax.jit(fn).lower(*args).compile().as_text()


def _has_kernel(hlo, name):
    """The program holds a Mosaic custom call whose instruction carries
    the kernel's ``name=``: what a Perfetto/XProf view of the device
    trace shows it as."""
    return re.search(r"%%%s(\.\d+)? = [^\n]*tpu_custom_call" % name,
                     hlo) is not None


# the LM of chip_smoke.py: d_model 2048, 16 heads (head_dim 128),
# B 8 x T 1024, amp bfloat16
_QKV = ((8, 16, 1024, 128), BF16)


def _flash(q, k, v):
    return pa.flash_attention(q, k, v, causal=True, interpret=False)


def _flash_seg(q, k, v, seg):
    return pa.flash_attention(q, k, v, causal=True, segment_ids=seg,
                              interpret=False)


def _flash_grad(q, k, v, *seg):
    # keep the forward's value live, as a train step does
    fn = _flash_seg if seg else _flash
    return jax.value_and_grad(
        lambda *a: fn(*a, *seg).astype(F32).sum(), argnums=(0, 1, 2))(
            q, k, v)


@pytest.mark.parametrize("fn,extra,name", [
    (_flash, (), "flash_attention_fwd"),
    (_flash_seg, (((8, 1024), I32),), "flash_attention_fwd_seg"),
    # under differentiation XLA names it from "jvp(flash_attention_fwd)"
    (_flash_grad, (), "jvp_flash_attention_fwd_"),
], ids=["causal", "causal_segment_ids", "custom_vjp_forward"])
def test_flash_attention_compiles(chip, fn, extra, name):
    assert _has_kernel(_compile(chip, fn, _QKV, _QKV, _QKV, *extra), name)


# the benchmark's training cells: [64, 2048, 128] bf16 a chip
_TRAIN_QKV = ((4, 16, 2048, 128), BF16)


@pytest.mark.parametrize("qkv", [
    _TRAIN_QKV,                          # the blocks _tiles picks for T 2,048
    ((8, 8, 1024, 64), BF16),            # two heads a lane tile
    ((2, 4, 1280, 128), F32),            # divisor blocks, float32 operands
], ids=["training_cell", "head_dim_64", "f32_divisor_blocks"])
@pytest.mark.parametrize("fn,seg,name", [
    (_flash, False, "flash_attention_fwd"),
    (_flash_seg, True, "flash_attention_fwd_seg"),
    (_flash_grad, False, "jvp_flash_attention_fwd_"),
], ids=["causal", "causal_segment_ids", "custom_vjp_forward"])
def test_flash_attention_forward_compiles_at(chip, fn, seg, name, qkv):
    """The forward kernel, alone, segmented and under differentiation (the
    row statistics written), at the other geometries its callers have."""
    (b, _, t, _), _ = qkv
    extra = (((b, t), I32),) if seg else ()
    # (a function of its own: a trace of ``fn`` that jit kept would leave
    # the kernel-path counters of a later test of this shape unmoved)
    assert _has_kernel(_compile(chip, lambda *a: fn(*a), qkv, qkv, qkv,
                                *extra), name)


@pytest.mark.parametrize("fn", [_flash, _flash_grad],
                         ids=["causal", "custom_vjp_forward"])
def test_flash_attention_forward_keeps_its_operands_bfloat16(chip, fn):
    """At the training cells' shape the compiled program around the
    forward kernel holds no float32 copy of q, k or v and no float32
    [.., T] array but the rows' statistics [BH, 1, T]: bfloat16 operands
    reach the kernel as they are."""
    hlo = _compile(chip, lambda *a: fn(*a), _TRAIN_QKV, _TRAIN_QKV,
                   _TRAIN_QKV)
    assert _has_kernel(hlo, "flash_attention_fwd") or \
        _has_kernel(hlo, "jvp_flash_attention_fwd_")
    assert not re.search(r"f32\[\d+,(?!1,)\d+,2048\]", hlo)
    if fn is _flash:
        assert not re.search(r"f32\[(64|4,16),2048,128\]", hlo)


def _paths(kernel, since=None):
    """Traced sites of ``kernel`` by path, less those of ``since`` (an
    earlier reading)."""
    now = kernel_path.counts().get(kernel, {})
    return {p: n - (since or {}).get(p, 0) for p, n in now.items()
            if n != (since or {}).get(p, 0)}


_bwd_paths = functools.partial(_paths, "flash_attention_bwd")
_paged_paths = functools.partial(_paths, "decode_attention_paged")


@pytest.mark.parametrize("qkv,extra,name", [
    (_TRAIN_QKV, (), "flash_attention_bwd_"),
    (_QKV, (((8, 1024), I32),), "flash_attention_bwd_seg_"),
    # two heads a lane tile: the blocks are whole in their last dim
    (((8, 8, 1024, 64), BF16), (), "flash_attention_bwd_"),
    (((2, 4, 1280, 128), F32), (), "flash_attention_bwd_"),
], ids=["training_cell", "segment_ids", "head_dim_64", "f32_divisor_blocks"])
def test_flash_attention_backward_compiles(chip, qkv, extra, name):
    """Under ``jax.grad`` the program holds the forward kernel (with its
    row statistics) and ``flash_attention_bwd``, both counted compiled,
    and nothing of a chunked XLA backward: no float32 array of a chunk's
    scores against every key."""
    before = _bwd_paths()
    hlo = _compile(chip, _flash_grad, qkv, qkv, qkv, *extra)
    assert _bwd_paths(before) == {"compiled": 1}
    # XLA names it from "transpose(jvp(flash_attention_bwd))"
    assert _has_kernel(hlo, "transpose_jvp_" + name + "_"), hlo[-3000:]
    assert _has_kernel(hlo, "jvp_" + name.replace("bwd", "fwd"))
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2
    t = qkv[0][2]
    # (the row statistics are [BH, 1, T]: one row, not a chunk's)
    assert not re.search(r"f32\[\d+,(?!1,)\d+,%d\]" % t, hlo), \
        "a float32 [.., T] array of scores in the program"


def test_flash_attention_backward_off_the_lane_tiles_takes_reference(chip):
    """T = 288 = 2 x 144 rows: the forward kernel tiles it, the row
    statistics (sliced along lanes a q block at a time) do not. The gate
    hands the gradient to the reference's vjp, counted ``xla``."""
    before = _bwd_paths()
    qkv = ((2, 4, 288, 128), BF16)
    hlo = _compile(chip, _flash_grad, qkv, qkv, qkv)
    assert _bwd_paths(before) == {"xla": 1}
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1


def _paged(chip, num_heads, head_dim, dtype, query=F32, slots=8,
           max_blocks=64, pool_blocks=8 * 64, num_kv_heads=None,
           window=None):
    dm = num_heads * head_dim
    # blocks of 16 rows, as wide as the heads the pool holds
    pool = ((pool_blocks, 16, (num_kv_heads or num_heads) * head_dim),
            dtype)

    def fn(q, kp, vp, lens, tables):
        return pa.decode_attention_paged(
            q, kp, vp, lens, tables, num_heads, interpret=False,
            num_kv_heads=num_kv_heads, window=window)
    return _compile(chip, fn, ((slots, 1, dm), query), pool, pool,
                    ((slots,), I32), ((slots, max_blocks), I32))


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
def test_decode_attention_paged_compiles(chip, dtype):
    assert _has_kernel(_paged(chip, 16, 128, dtype),
                       "decode_attention_paged")


def test_decode_attention_paged_compiles_at_serving_geometry(chip):
    """The benchmark's serving cells (benchmarks/workloads/lm-serve-*):
    32 slots, bf16 query, 2,048 bf16 blocks of 16 x 2048, table rows of
    128; 8 pages a compute block, 2 MB of page buffers."""
    assert _has_kernel(
        _paged(chip, 16, 128, BF16, query=BF16, slots=32,
               max_blocks=128, pool_blocks=2048),
        "decode_attention_paged")


@pytest.mark.parametrize("window", [None, 2048], ids=["full", "window"])
def test_decode_attention_paged_compiles_grouped_queries(chip, window):
    """trinity-serve-offline's two kinds of layer: 64 slots, 32 query
    heads on 4 KV heads of 128, float32 query and pools 512 wide (products
    at the highest precision), table rows of 512; the same kernel, with
    and without the window in its page walk. A block of its walk is 16
    pages, awaited once a pool where all are live."""
    before = _paged_paths()
    assert _has_kernel(
        _paged(chip, 32, 128, F32, query=F32, slots=64,
               max_blocks=512, pool_blocks=8704, num_kv_heads=4,
               window=window),
        "decode_attention_paged")
    assert _paged_paths(before) == {"compiled": 1}
    assert pa._paged_block_pages(16, 512, F32, 512) == 16


def test_decode_attention_paged_compiles_at_nemotrons_geometry(chip):
    """nemotron-serve-offline's attention layers: 128 slots, 32 query
    heads on 2 KV heads of 128, float32 pools 256 wide in blocks of 16
    rows (a page is 16 KB, the smallest served), table rows of 256: a
    block of the walk is 32 pages of K and 32 of V, 64 copies awaited in
    two waits."""
    before = _paged_paths()
    assert _has_kernel(
        _paged(chip, 32, 128, F32, query=F32, slots=128,
               max_blocks=256, pool_blocks=32768, num_kv_heads=2),
        "decode_attention_paged")
    assert _paged_paths(before) == {"compiled": 1}
    assert pa._paged_block_pages(16, 256, F32, 256) == 32


@pytest.mark.parametrize("slots,window,pool_blocks", [
    (64, None, 32768), (64, 512, 2560), (1, None, 32768)],
    ids=["full_and_cross_layers", "window_layers", "a_prefills_cross_walk"])
def test_decode_attention_paged_compiles_differential_pairs(
        chip, slots, window, pool_blocks):
    """phi4flash-serve-offline's walks: a differential pair's two queries
    as two heads of 128 lanes on the pair's one KV head ``(k1 | k2)``, so
    40 query heads of 128 on 10 KV heads, float32 query, bfloat16 pools
    1,280 wide in blocks of 16 rows, table rows of 512: the full layer's
    and the seven cross layers' walk, the window layers' behind a window of
    512, and the one-slot walk a prefill's cross layers make."""
    before = _paged_paths()
    assert _has_kernel(
        _paged(chip, 40, 128, BF16, query=F32, slots=slots, max_blocks=512,
               pool_blocks=pool_blocks, num_kv_heads=10, window=window),
        "decode_attention_paged")
    assert _paged_paths(before) == {"compiled": 1}


@pytest.mark.parametrize("aligned", [True, False], ids=["window", "chunks"])
def test_decode_attention_paged_compiles_a_walk_to_be_merged(chip, aligned):
    """evabyte-serve-offline's two walks a layer (ops/eva_ops.py): 24
    slots, 32 heads of 128 each on its own KV head, bfloat16 query and
    pools 4,096 wide, table rows of 768; the window pool's walk aligned,
    the chunk pool's plain, each with its maximum and sum behind the
    result (float32, [24, 32, 128] lanes of statistics)."""
    def fn(q, kp, vp, lens, tables):
        return pa.decode_attention_paged(
            q, kp, vp, lens, tables, 32, interpret=False,
            window=2048 if aligned else None, aligned=aligned, stats=True)
    pool = ((3072 if aligned else 1152, 16, 4096), BF16)
    before = _paged_paths()
    hlo = _compile(chip, fn, ((24, 1, 4096), BF16), pool, pool,
                   ((24,), I32), ((24, 768), I32))
    assert _has_kernel(hlo, "decode_attention_paged")
    assert _paged_paths(before) == {"compiled": 1}
    assert re.search(r"f32\[24,1,4096\]", hlo) and \
        re.search(r"f32\[24,32,128\]", hlo), hlo[-2000:]


@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
def test_flash_attention_forward_compiles_with_its_statistics(chip, dtype):
    """evabyte-serve-offline's prefill (ops/eva_ops.py): the four windows
    of an 8,192 bucket under 32 heads are 128 causal sequences of 2,048
    rows; the kernel hands back its float32 sums unrounded and every
    row's log-sum-exp, in tiles the chip's compiler accepts."""
    def fn(q, k, v):
        return pa.flash_attention_stats(q, k, v, causal=True,
                                        interpret=False)
    qkv = ((128, 2048, 128), dtype)
    hlo = _compile(chip, fn, qkv, qkv, qkv)
    assert _has_kernel(hlo, "flash_attention_fwd")
    call = re.search(r"%flash_attention_fwd(?:\.\d+)? = [^\n]*", hlo).group()
    assert "f32[128,2048,128]" in call and "f32[128,1,2048]" in call


def _evabyte_program(chip, program):
    """evabyte-serve-offline's decode program or a prefill bucket as the
    executor compiles it for the described chip (``tools/hlo_census.py``):
    the configuration at its published widths and its deployment's 24
    slots and pools, cut to two layers (every layer compiles alike). ->
    ((memory, HLO), the kernel paths counted, the pools' names, the rotary
    turns counted fenced)."""
    from benchmarks.harness import common
    patch = pytest.MonkeyPatch()
    patch.setattr(kernel_path, "interpret_mode", lambda: False)
    before = kernel_path.counts()
    try:
        mem, hlo, pools, fenced = hlo_census.compile_program(
            "evabyte-6.5b-l8", 2, program, chip)
    finally:
        patch.undo()
    return (mem, hlo), common.kernel_paths_since(before), pools, fenced


@pytest.fixture(scope="module")
def evabyte_prefill(chip):
    """The 8,192 bucket (feeds ``gen.ptok``, ``.plen``, ``.ppos``,
    ``.phist``, ``.ppix`` and one ``[768]`` table a cache kind)."""
    return _evabyte_program(chip, 8192)


@pytest.fixture(scope="module")
def evabyte_decode(chip):
    """The decode program (feeds ``gen.dtok``, ``gen.dpos`` and one ``[24,
    768]`` table a cache kind)."""
    return _evabyte_program(chip, "decode")


def test_evabyte_prefill_attends_its_windows_through_the_flash_forward(
        evabyte_prefill):
    """8,192 positions: one Mosaic call a layer over the 4 x 32 windows
    (the float32 result and the rows' statistics), counted ``compiled``
    once a layer, building the programs counted nothing (the op says its
    output's shape), no ``xla``; the XLA form's scores ``[32, 256, 2048 +
    512]`` are gone and the summaries' are a window's rows against the
    128, 256 and 384 summaries before it, never all rows against all
    (403 MB). The temporaries stay what the pools' scatter makes them
    (1,150,553,088 B on the parent, my sandbox compile, PR 43), in
    neither attention."""
    (mem, hlo), paths = evabyte_prefill[:2]
    calls = re.findall(r"%flash_attention_fwd(?:\.\d+)? = [^\n]*"
                       r"tpu_custom_call", hlo)
    assert len(calls) == 2
    assert all("f32[128,2048,128]" in c and "f32[128,1,2048]" in c
               for c in calls)
    assert paths["flash_attention"] == {"compiled": 2}
    assert not re.search(r"f32\[32,256,\d+\]", hlo)
    for summaries in (128, 256, 384):
        assert "f32[32,2048,%d]" % summaries in hlo
    assert not re.search(r"f32\[(1,)?32,8192,512\]", hlo)
    assert mem["temp_bytes"] < 1.16e9, mem


def test_evabyte_summaries_pool_the_rows_as_they_lie(evabyte_prefill):
    """PR 44: ``eva_summaries`` never splits the rows into heads, so the
    8,192 bucket holds no ``copy`` and no stand-alone ``convert`` that
    makes a float32 array of the rows' size (8,192 x 32 x 128: the parent
    held two such copies a layer, 134 MB each) under ``eva.summaries``;
    the scores of both poolings are one product a layer (``[512, 16, 64]``
    with its softmax in the product's fusion) and the weights' way back
    over the lanes, the weighted sum over the rows and the cast are one
    ``kOutput`` fusion a pooling; no rotary turn of a prefill is
    fenced."""
    (mem, hlo), _, pools, fenced = evabyte_prefill
    rows = hlo_census.census(hlo, pools, min_bytes=1 << 20)
    assert rows and all(r["op"] in hlo_census.OPCODES for r in rows)
    # under eva.summaries or anywhere else in the bucket
    assert not [r for r in rows if r["shape"].startswith("f32[")
                and r["bytes"] >= 8192 * 32 * 128 * 4], rows
    entry = re.sub(r"\{[\d,]*(?::[^}]*)?\}", "", hlo[hlo.index("\nENTRY "):])
    products = re.findall(r" = ([^=\n]*) fusion\([^\n]*kind=kOutput[^\n]*"
                          r"eva\.summaries", entry)
    assert sum("f32[512,16,64]" in p for p in products) == 2, products
    assert products.count("bf16[512,4096]") == 4, products
    assert fenced == 0
    assert mem["temp_bytes"] < 1.16e9, mem


def test_evabyte_decode_step_reads_its_weights_where_they_lie(
        evabyte_decode):
    """PR 44: with the rotary turns' rows fenced (``moe_ops._fence_rows``,
    counted 4: q and k of two layers) the decode step copies no ``[4096,
    4096]`` projection into another layout (the parent: ``attn.q.w`` and
    ``attn.k.w`` of every layer, 33.5 MB each, ``temp_bytes``
    35,844,608) and holds no layout work of a megabyte at all, its
    pooling of the newest blocks included; the first layer's q, k and v
    weights are each read by the ``kOutput`` fusion of their product as
    they lie; two walks a layer as before."""
    (mem, hlo), paths, pools, fenced = evabyte_decode
    assert fenced == 4
    rows = hlo_census.census(hlo, pools)
    assert not [r for r in rows if r["shape"] == "bf16[4096,4096]"], rows
    assert not [r for r in rows if r["from"] == "parameter"], rows
    assert not [r for r in rows if "eva_summaries" in
                r["op_name"] + r["source"]], rows
    assert mem["temp_bytes"] < 4 << 20, mem
    entry = hlo[hlo.index("\nENTRY "):]
    for part in "qkv":
        assert re.search(
            r" fusion\(%%state_ro__moe_lm_l0_attn_%s_w__[^\n]*kind=kOutput"
            % part, entry), part
    assert len(re.findall(r"%decode_attention_paged(?:\.\d+)? = [^\n]*"
                          r"tpu_custom_call", hlo)) == 4
    assert set(paths["decode_attention_paged"]) == {"compiled"}


def test_exact_products_keep_their_three_pieces(chip):
    """ops/moe_ops.py exact_dot on the chip's compiler: the float32
    activation reaches the bfloat16 weight as 3 x the rows in one
    bfloat16 product (the compiler has not folded the pieces away), and
    the grouped form runs in kernels of the compiler's own."""
    from paddle_tpu.ops import moe_ops
    hlo = _compile(chip, moe_ops.exact_dot, ((64, 2048), F32),
                   ((2048, 4096), BF16))
    assert "reduce-precision" in hlo
    assert re.search(r"bf16\[192,2048\]", hlo), hlo[-3000:]
    hlo = _compile(chip, moe_ops.exact_ragged_dot, ((512, 2048), F32),
                   ((128, 2048, 1024), BF16), ((128,), I32))
    assert "reduce-precision" in hlo and "bf16[1536,2048]" in hlo


def _moe_ffn(monkeypatch, chip, tokens):
    """HLO of the ``moe_ffn`` op at Trinity-Mini's widths (top-8 of 128
    experts of 2048 x 1024 held in bfloat16) for ``tokens`` tokens, and the
    paths it counted meanwhile. The op asks ``interpret_mode()``: answered
    as a chip would."""
    from types import SimpleNamespace
    from paddle_tpu.core.registry import ExecContext
    from paddle_tpu.ops import moe_ops
    monkeypatch.setattr(kernel_path, "interpret_mode", lambda: False)
    op = SimpleNamespace(attrs={"num_experts": 128, "top_k": 8})
    slots = ("X", "RouterW", "ExpertBias", "WGate", "WUp", "WDown")

    def fn(*values):
        return moe_ops._moe_ffn(ExecContext(
            op, {slot: [v] for slot, v in zip(slots, values)}))
    before = dict(kernel_path.counts().get("moe_grouped_matmul", {}))
    hlo = _compile(chip, fn, ((tokens, 2048), F32), ((2048, 128), F32),
                   ((128,), F32), ((128, 2048, 1024), BF16),
                   ((128, 2048, 1024), BF16), ((128, 1024, 2048), BF16))
    after = kernel_path.counts()["moe_grouped_matmul"]
    return hlo, {p: n - before.get(p, 0) for p, n in after.items()
                 if n != before.get(p, 0)}


@pytest.mark.parametrize("tokens", [64, 4096], ids=["decode_step",
                                                    "largest_prefill"])
def test_expert_matmuls_compile_as_a_weight_stream(chip, monkeypatch,
                                                   tokens):
    """trinity-serve-offline's decode step (512 pairs, 4 an expert) and its
    4,096-token prefill (256 an expert, the most the gate admits): two
    Mosaic calls named ``moe_grouped_matmul``, gate and up in one and down
    in the other, none of the compiler's own ``ragged-dot`` kernels, and
    the three pieces taken in the kernel (no ``reduce-precision`` and no
    tripled rows outside it)."""
    hlo, paths = _moe_ffn(monkeypatch, chip, tokens)
    assert paths == {"compiled": 1}
    calls = re.findall(r"%moe_grouped_matmul(?:\.\d+)? = [^\n]*"
                       r"tpu_custom_call", hlo)
    assert len(calls) == 2, hlo[-3000:]
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2
    assert "ragged" not in hlo and "reduce-precision" not in hlo
    assert "bf16[%d,2048]" % (3 * tokens * 8) not in hlo


def test_expert_matmuls_past_the_measured_sizes_take_ragged_dot(
        chip, monkeypatch):
    """8,192 tokens are 512 pairs an expert, twice the most measured: the
    gate hands them to ``exact_ragged_dot``, counted ``xla``, three pieces
    and the compiler's own kernels as before."""
    hlo, paths = _moe_ffn(monkeypatch, chip, 8192)
    assert paths == {"xla": 1}
    assert not _has_kernel(hlo, "moe_grouped_matmul")
    assert "reduce-precision" in hlo and "bf16[196608,2048]" in hlo


@pytest.mark.parametrize("block_size", [32, 16])
def test_decode_attention_paged_compiles_on_a_latent_pool(chip, block_size):
    """kimi-serve-offline's layer cache: 32 slots, 64 query heads on ONE KV
    head whose value is the leading 512 lanes of its key's 640-lane
    float32 row; one pool, fetched once a page; the output [64, 512] a
    slot."""
    max_blocks = 8192 // block_size

    def fn(q, pool, lens, tables):
        return pa.decode_attention_paged(
            q, pool, None, lens, tables, 64, interpret=False,
            num_kv_heads=1, v_width=512, scale=0.1447)
    before = _paged_paths()
    hlo = _compile(chip, fn, ((32, 1, 64 * 640), F32),
                   ((32 * max_blocks, block_size, 640), F32), ((32,), I32),
                   ((32, max_blocks), I32))
    assert _has_kernel(hlo, "decode_attention_paged")
    assert _paged_paths(before) == {"compiled": 1}
    assert re.search(r"f32\[32,64,512\]", hlo), hlo[-2000:]


def test_a_576_wide_latent_pool_takes_the_reference(chip):
    """Four and a half lane tiles: the gate hands it to the XLA gather."""
    before = kernel_path.counts().get("decode_attention_paged", {})
    hlo = _compile(
        chip, lambda q, pool, lens, tables: pa.decode_attention_paged(
            q, pool, None, lens, tables, 64, interpret=False,
            num_kv_heads=1, v_width=512),
        ((4, 1, 64 * 576), F32), ((64, 32, 576), F32), ((4,), I32),
        ((4, 16), I32))
    after = kernel_path.counts()["decode_attention_paged"]
    assert not _has_kernel(hlo, "decode_attention_paged")
    assert after.get("xla", 0) == before.get("xla", 0) + 1


@pytest.mark.parametrize("tokens", [32, 4096], ids=["decode_step",
                                                    "largest_prefill"])
def test_a_shares_expert_matmuls_compile_as_a_weight_stream(
        chip, monkeypatch, tokens):
    """kimi-serve-offline: 12 of 384 experts of 7168 x 2048 held in
    bfloat16 (29 MB an expert: the column-tiled path), top-8. The decode
    step's 256 pairs in one pass; the 4,096-token prefill's 32,768 in
    passes of 2,048 rows inside a loop. Both in ``moe_grouped_matmul``,
    none in ``ragged_dot``."""
    from types import SimpleNamespace
    from paddle_tpu.core.registry import ExecContext
    from paddle_tpu.ops import moe_ops
    monkeypatch.setattr(kernel_path, "interpret_mode", lambda: False)
    op = SimpleNamespace(attrs={"num_experts": 384, "top_k": 8,
                                "route_scale": 2.827})
    slots = ("X", "RouterW", "ExpertBias", "WGate", "WUp", "WDown")

    def fn(*values):
        return moe_ops._moe_ffn(ExecContext(
            op, {slot: [v] for slot, v in zip(slots, values)}))
    before = dict(kernel_path.counts().get("moe_grouped_matmul", {}))
    hlo = _compile(chip, fn, ((tokens, 7168), F32), ((7168, 384), F32),
                   ((384,), F32), ((12, 7168, 2048), BF16),
                   ((12, 7168, 2048), BF16), ((12, 2048, 7168), BF16))
    after = kernel_path.counts()["moe_grouped_matmul"]
    assert {p: n - before.get(p, 0) for p, n in after.items()
            if n != before.get(p, 0)} == {"compiled": 1}
    assert len(re.findall(r"%moe_grouped_matmul(?:\.\d+)? = [^\n]*"
                          r"tpu_custom_call", hlo)) == 2, hlo[-3000:]
    assert "ragged" not in hlo and "reduce-precision" not in hlo
    # the rows gathered are a pass's, not every pair's
    if tokens == 4096:
        assert "f32[32768,7168]" not in hlo and "f32[2048,7168]" in hlo


def test_decode_attention_paged_head_dim_64_takes_reference(chip):
    """d_model 512 / 8 heads: two heads share a lane tile, a geometry
    the kernel has not run compiled. The gate must hand it to the XLA
    gather — counted, compiled, and with no kernel in the program —
    not let it reach the lowering."""
    before = kernel_path.counts().get(
        "decode_attention_paged", {}).get("xla", 0)
    assert "tpu_custom_call" not in _paged(chip, 8, 64, F32)
    assert kernel_path.counts()["decode_attention_paged"]["xla"] == \
        before + 1


@pytest.mark.parametrize("k,n", [(2048, 8192), (8192, 2048),
                                 (2048, 32768)])
def test_int8_matmul_compiles(chip, k, n):
    def fn(x, wq, ws):
        return quant_ops._pallas_int8_matmul(x, wq, ws, interpret=False)
    assert "tpu_custom_call" in _compile(
        chip, fn, ((8, k), F32), ((k, n), I8), ((n,), F32))


@pytest.mark.parametrize("c,hw,o", [(512, 28, 128), (256, 14, 1024)],
                         ids=["conv3_x_reduce", "conv4_x_expand"])
def test_conv1x1_bn_compiles(chip, c, hw, o):
    """ResNet-50 bottleneck 1x1 convs at batch 256, f32 activations."""
    def fn(x, w):
        return pallas_conv_bn._pallas_1x1(x, w, interpret=False)
    assert "tpu_custom_call" in _compile(
        chip, fn, ((256, c, hw, hw), F32), ((o, c, 1, 1), F32))


@pytest.fixture(scope="module")
def granite_programs(chip):
    """granite-serve-offline's programs as the executor compiles them, for
    the described chip: the configuration at its published widths, its
    deployment's 96 slots and 24,576 blocks, cut to one state-space layer
    and the attention layer (every layer of a kind compiles alike).
    -> {"decode" | 2048: (memory, HLO)}, and the kernel paths counted."""
    import numpy as np
    import paddle_tpu as ptpu
    from benchmarks import architectures
    from benchmarks.harness import lm
    from benchmarks.sweeps import sizing
    cfg = lm.load_config("granite-4.0-h-small-l10")
    cfg.update(num_hidden_layers=2, layer_types=["mamba", "attention"])
    arch = architectures.load(cfg)
    geometry = cfg["deployment"]["serving"]
    patch = pytest.MonkeyPatch()
    patch.setattr(kernel_path, "interpret_mode", lambda: False)
    before = {k: dict(v) for k, v in kernel_path.counts().items()}
    out = {}
    try:
        with lm.flags(generation_kv_dtype=geometry["kv_dtype"],
                      matmul_precision="BF16_BF16_F32", **cfg["flags"]):
            with ptpu.unique_name.guard():
                startup = arch.serve_startup(cfg, 0)
            spec = arch.serve_spec(cfg, geometry, (2048,))
            scope = sizing._ShapeScope([startup], more=spec.cache_vars)
            exe = ptpu.Executor()
            s, mb = spec.slots, spec.max_blocks
            feed = {"gen.dtok": np.zeros((s, 1), "int64"),
                    "gen.dpos": np.zeros((s,), "int32"),
                    "gen.dtab": np.zeros((s, mb), "int32"),
                    "gen.dtab.state": np.zeros((s, 1), "int32")}
            out["decode"] = sizing._compile(
                exe, spec.decode_program, feed,
                [spec.decode_fetch, spec.stats_fetch], scope, chip)
            feed = {"gen.ptok": np.zeros((1, 2048), "int64"),
                    "gen.plen": np.ones((1,), "int32"),
                    "gen.ppos": np.zeros((1,), "int32"),
                    "gen.phist": np.zeros((1,), "int32"),
                    "gen.ppix": np.zeros((2048,), "int32"),
                    "gen.ptab": np.zeros((mb,), "int32"),
                    "gen.ptab.state": np.zeros((1,), "int32")}
            out[2048] = sizing._compile(
                exe, spec.prefill_programs[2048], feed, [spec.prefill_fetch],
                scope, chip)
    finally:
        patch.undo()
    after = kernel_path.counts()
    paths = {k: {p: n - before.get(k, {}).get(p, 0) for p, n in v.items()
                 if n != before.get(k, {}).get(p, 0)}
             for k, v in after.items()}
    return out, {k: v for k, v in paths.items() if v}


def test_granite_decode_step_updates_the_state_where_it_lies(
        granite_programs):
    """96 slots: the state pool of the layer (403 MB) is an argument that
    the step's output aliases, and the temporaries hold no second copy of
    it; the attention layer's paged decode and the held experts' grouped
    matmuls are Mosaic calls; the tied head reads the embedding
    [25088, 4096] as it lies: no transposed copy of its 205 MB."""
    (mem, hlo), paths = granite_programs[0]["decode"], granite_programs[1]
    state = 96 * 4 * (128 * 64 * 128 + 4 * 8448)
    assert mem["alias_bytes"] > state
    assert mem["temp_bytes"] < state // 4, mem
    assert mem["temp_bytes"] < 25088 * 4096 * 2, mem
    assert _has_kernel(hlo, "decode_attention_paged")
    assert len(re.findall(r"%moe_grouped_matmul(?:\.\d+)? = [^\n]*"
                          r"tpu_custom_call", hlo)) == 2 * 2
    assert "f32[96,128,64,128]" in hlo
    assert not re.search(r"bf16\[4096,25088\]", hlo)
    assert "ragged" not in hlo
    assert set(paths["moe_grouped_matmul"]) == {"compiled"}
    assert set(paths["decode_attention_paged"]) == {"compiled"}


def test_granite_prefill_compiles_with_its_chunks_and_its_passes(
        granite_programs):
    """The 2,048 bucket: 8 chunks of 256 rows under 128 heads (the scores
    ``[8, 128, 256, 256]``), the state written into the slot's row of the
    pool in place, and the held experts' 5,120 expected pairs taken in
    passes of ``SHARE_ROWS`` 2,048 rows (three in balance) by the kernel
    inside a loop."""
    mem, hlo = granite_programs[0][2048]
    state = 96 * 4 * (128 * 64 * 128 + 4 * 8448)
    assert mem["alias_bytes"] > state
    assert mem["temp_bytes"] < 1.2e9, mem
    assert "f32[8,128,256,256]" in hlo
    assert _has_kernel(hlo, "moe_grouped_matmul")
    assert "f32[20480,4096]" not in hlo and "f32[2048,4096]" in hlo
    assert "ragged" not in hlo


@pytest.fixture(scope="module")
def nemotron_programs(chip):
    """nemotron-serve-offline's programs as the executor compiles them, for
    the described chip: the configuration at its published widths, its
    deployment's 128 slots and 32,768 blocks, cut to one layer of each
    letter, ``ME*`` (every layer of a letter compiles alike).
    -> {"decode" | 2048: (memory, HLO)}, and the kernel paths counted."""
    import numpy as np
    import paddle_tpu as ptpu
    from benchmarks import architectures
    from benchmarks.harness import lm
    from benchmarks.sweeps import sizing
    cfg = lm.load_config("nemotron-3-nano-30b-a3b-l13")
    cfg.update(num_hidden_layers=3, hybrid_override_pattern="ME*")
    arch = architectures.load(cfg)
    geometry = cfg["deployment"]["serving"]
    patch = pytest.MonkeyPatch()
    patch.setattr(kernel_path, "interpret_mode", lambda: False)
    before = {k: dict(v) for k, v in kernel_path.counts().items()}
    out = {}
    try:
        with lm.flags(generation_kv_dtype=geometry["kv_dtype"],
                      matmul_precision="BF16_BF16_F32", **cfg["flags"]):
            with ptpu.unique_name.guard():
                startup = arch.serve_startup(cfg, 0)
            spec = arch.serve_spec(cfg, geometry, (2048,))
            scope = sizing._ShapeScope([startup], more=spec.cache_vars)
            exe = ptpu.Executor()
            s, mb = spec.slots, spec.max_blocks
            feed = {"gen.dtok": np.zeros((s, 1), "int64"),
                    "gen.dpos": np.zeros((s,), "int32"),
                    "gen.dtab": np.zeros((s, mb), "int32"),
                    "gen.dtab.state": np.zeros((s, 1), "int32")}
            out["decode"] = sizing._compile(
                exe, spec.decode_program, feed,
                [spec.decode_fetch, spec.stats_fetch], scope, chip)
            feed = {"gen.ptok": np.zeros((1, 2048), "int64"),
                    "gen.plen": np.ones((1,), "int32"),
                    "gen.ppos": np.zeros((1,), "int32"),
                    "gen.phist": np.zeros((1,), "int32"),
                    "gen.ppix": np.zeros((2048,), "int32"),
                    "gen.ptab": np.zeros((mb,), "int32"),
                    "gen.ptab.state": np.zeros((1,), "int32")}
            out[2048] = sizing._compile(
                exe, spec.prefill_programs[2048], feed, [spec.prefill_fetch],
                scope, chip)
    finally:
        patch.undo()
    after = kernel_path.counts()
    paths = {k: {p: n - before.get(k, {}).get(p, 0) for p, n in v.items()
                 if n != before.get(k, {}).get(p, 0)}
             for k, v in after.items()}
    return out, {k: v for k, v in paths.items() if v}


def test_nemotron_decode_step_streams_its_experts_as_they_lie(
        nemotron_programs):
    """128 slots: the held expert layer's two products are two Mosaic
    calls named ``moe_grouped_matmul`` over the stacks ``[64, 1856, 2688]``
    as they lie (no copy of either 319 MB stack, which held ``[64, 2688,
    1856]`` the compiler made every step; no ``ragged_dot`` fallback), the
    attention layer's paged decode is a Mosaic call, and the mixer's state
    pool (281 MB) is an argument that the step's output aliases."""
    (mem, hlo), paths = nemotron_programs[0]["decode"], nemotron_programs[1]
    stack = 64 * 1856 * 2688 * 2
    state = 128 * 4 * (64 * 64 * 128 + 4 * 6144)
    assert mem["alias_bytes"] > state
    assert mem["temp_bytes"] < stack // 2, mem
    calls = re.findall(r"%moe_grouped_matmul(?:\.\d+)? = [^\n]*"
                       r"tpu_custom_call[^\n]*", hlo)
    assert len(calls) == 2 * 1, hlo[-3000:]
    assert all("bf16[64,1856,2688]{2,1,0}" in c for c in calls)
    assert "f32[768,1856]" in calls[0] + calls[1]
    assert not re.search(r"bf16\[64,2688,1856\]", hlo)
    assert not re.search(r"= bf16\[64,1856,2688\][^\n]* copy\(", hlo)
    assert _has_kernel(hlo, "decode_attention_paged")
    assert "f32[128,64,64,128]" in hlo
    assert "ragged" not in hlo
    assert set(paths["moe_grouped_matmul"]) == {"compiled"}
    assert set(paths["decode_attention_paged"]) == {"compiled"}


def test_nemotron_prefill_compiles_with_its_groups_and_its_passes(
        nemotron_programs):
    """The 2,048 bucket: 16 chunks of 128 rows, the scores of each of the
    eight groups, the state written into the slot's row of the pool in
    place, and the held experts' 6,144 expected pairs taken in passes of
    ``SHARE_ROWS`` 2,048 rows by the kernel inside a loop."""
    mem, hlo = nemotron_programs[0][2048]
    state = 128 * 4 * (64 * 64 * 128 + 4 * 6144)
    assert mem["alias_bytes"] > state
    assert mem["temp_bytes"] < 1.0e9, mem
    assert _has_kernel(hlo, "moe_grouped_matmul")
    assert "f32[2048,1856]" in hlo and "f32[12288,2688]" not in hlo
    assert re.search(r"f32\[(8,16|16,8),128,128\]", hlo), \
        "the groups' chunk scores"
    assert not re.search(r"= bf16\[64,1856,2688\][^\n]* copy\(", hlo)
    assert "ragged" not in hlo


def _update_fusions(chip):
    """The FFN's down projection at the training cells' shape (8,192 rows
    of 8,192 -> 2,048, amp bfloat16) with its ``vjp_grad`` and ``adam``,
    compiled through the executor's own trace -> the kinds of the fusions
    whose result tuple is ParamOut and both moments."""
    import numpy as np
    import paddle_tpu as ptpu
    from paddle_tpu import layers
    from benchmarks.harness import lm
    from benchmarks.sweeps import sizing
    main, startup = ptpu.Program(), ptpu.Program()
    with ptpu.unique_name.guard(), ptpu.program_guard(main, startup):
        x = layers.data("x", shape=[8192])
        y = layers.fc(x, 2048, bias_attr=False)
        loss = layers.reduce_mean(layers.square(y))
        ptpu.optimizer.Adam(learning_rate=1e-4).minimize(
            loss, startup_program=startup)
    assert sum(op.type == "adam" for op in main.global_block().ops) == 1
    with lm.flags(amp="bfloat16", matmul_precision="BF16_BF16_F32"):
        _, hlo = sizing._compile(
            ptpu.Executor(), main, {"x": np.zeros((8192, 8192), "float32")},
            [loss], sizing._ShapeScope([main, startup]), chip)
    w = r"f32\[8192,2048\]"
    return re.findall(r"= \(%s, %s, %s\) fusion\([^\n]*kind=(k\w+)"
                      % (w, w, w), re.sub(r"\{[^}]*\}", "", hlo))


def test_adam_compiles_apart_from_the_weight_gradient_matmul(
        chip, monkeypatch):
    """PR 34: with the gradient fenced, the weight-gradient product is a
    fusion of its own and Adam an elementwise (``kLoop``) pass over the
    parameter and its moments; unfenced, XLA makes the update the
    product's epilogue (``kOutput``), which the chip runs at 44-60% of
    the MXU's peak (PERF.md §6)."""
    from paddle_tpu.core import executor
    assert _update_fusions(chip) == ["kLoop"]
    monkeypatch.setattr(executor, "_fence_update_grad",
                        lambda op, values: None)
    assert _update_fusions(chip) == ["kOutput"]


def _narrow_lm_step(chip, strategy):
    """(executor's entry, HLO text) of a two-layer GPT-2 block's training
    step at narrow widths (d 1024, eight heads of 128, FFN 4096: its two
    matrices' bfloat16 gradients are 8 MiB each, past the 4 MiB under
    which the strategy's options let all-reduces be combined; B 8 x T 256,
    amp bfloat16, flash kernels), compiled through the executor's own
    trace for the described chips: one, or the strategy's mesh."""
    import numpy as np
    import paddle_tpu as ptpu
    from paddle_tpu import layers
    from paddle_tpu.models.transformer import transformer_lm
    from benchmarks.harness import lm
    from benchmarks.sweeps import sizing
    main, startup = ptpu.Program(), ptpu.Program()
    with ptpu.unique_name.guard(), ptpu.program_guard(main, startup):
        toks = layers.data("toks", shape=[256], dtype="int64")
        lbls = layers.data("lbls", shape=[256], dtype="int64")
        loss, _ = transformer_lm(toks, lbls, vocab_size=512, d_model=1024,
                                 num_heads=8, d_ff=4096, num_layers=2)
        ptpu.optimizer.Adam(learning_rate=1e-4).minimize(
            loss, startup_program=startup)
    feed = {"toks": np.zeros((8, 256), "int32"),
            "lbls": np.zeros((8, 256), "int32")}
    exe = ptpu.Executor(strategy=strategy)
    with lm.flags(amp="bfloat16", flash_attention=True,
                  matmul_precision="BF16_BF16_F32"):
        _, hlo = sizing._compile(exe, main, feed, [loss],
                                 sizing._ShapeScope([main, startup]), chip)
    (entry,) = exe._cache.values()
    return entry, hlo


def _shape_strategy(devices):
    """DataParallel over the four described chips, placing shapes in
    place of arrays (a described device holds none)."""
    from benchmarks.architectures import gpt2_block
    from benchmarks.sweeps import sizing
    return sizing._shape_strategy(gpt2_block, None, {"data": 4}, devices)


def test_data_parallel_step_keeps_its_all_reduces_asynchronous(
        described, chip, monkeypatch):
    """PR 38: under a strategy whose batch is sharded over TPU chips the
    executor compiles the step with the strategy's compiler options, and
    the scheduled module keeps gradient all-reduces as pairs of fusions
    (``async-collective-start`` / ``-done``: the TPU's asynchronous form)
    with other fusions between a start and its done. With no strategy the
    same program is compiled with no option and holds no collective."""
    from paddle_tpu import parallel
    monkeypatch.setattr(kernel_path, "interpret_mode", lambda: False)
    strategy = _shape_strategy(described)
    assert strategy.compiler_options() == parallel._OVERLAPPED_ALL_REDUCE
    entry, hlo = _narrow_lm_step(chip, strategy)
    assert entry.options == strategy.compiler_options()
    # the four FFN matrices' gradients at least; the narrower ones are
    # combined, and a combined all-reduce is merged back into a plain one
    assert parallel.async_collectives(hlo) >= 4
    body = hlo[hlo.index("\nENTRY "):]
    between = re.findall(
        r"%async-collective-start(?:\.\d+)? = (.*?)"
        r"%async-collective-done(?:\.\d+)? = ", body, re.S)
    assert len(between) >= 4 and all(" fusion(" in b for b in between)

    entry, hlo = _narrow_lm_step(chip, None)
    assert entry.options == {}
    assert "all-reduce" not in hlo and "all-gather" not in hlo
    assert parallel.async_collectives(hlo) == 0
