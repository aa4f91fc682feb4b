#!/usr/bin/env python3
"""Does the sparse-expert LM disagree with its reference by a fault, or by
the router's choices?

Top-k of scores is a discontinuous choice: a program picks another expert
than the float32 reference wherever two scores lie closer than its noise,
and that position's logits then differ by far more than rounding.
``harness/serve.py`` holds the worst of 24 positions to ``LOGIT_RTOL``, so
it cannot tell such a choice from a fault. With bfloat16 activations that
failed every run of the cell (PERF.md section 6, PR 27), which is why the
program's products are exact now (``ops/moe_ops.py``); this script is what
measured it, and what to run if the cell's ``correct`` reads false again:
how far the margins lie above the noise that is left, and whether an error
is a choice or a fault. On the chip, in three parts (each a process of its
own):

``--part ffn``      the ``moe_ffn`` op alone at the configuration's widths
                    against the same selections computed expert by expert
                    (no sort, no groups), under natural routing, with every
                    second expert empty and with all tokens on 8 experts.
``--part model``    the harness's own check (a prompt per bucket, 8 decode
                    steps through both cache kinds), with the inputs of every
                    ``moe_ffn`` fetched beside the logits. Per compared
                    position: the program's and the reference's experts, the
                    reference's margin between the two where they differ,
                    the noise in the program's scores, the error against the
                    reference, and the error against the reference **handed
                    the program's selections** (``reference.afmoe.routed``).
                    Over all prompt rows: the share of decisions that differ.
``--part float32``  the harness's ``Deployment`` and its unedited check on a
                    float32-held cut at the published widths with matmuls at
                    the highest precision: what is left when the noise is
                    taken away is the program's logic. ``--kernel 0`` takes
                    the XLA gather in place of ``decode_attention_paged``.

    python3 benchmarks/sweeps/routing_agreement.py --part model \\
        --config trinity-mini-l5 --seed 2700000001 --sets 4

Every line of output is one JSON object; the last is the summary.
"""

import argparse
import copy
import json
import os
import sys
import time

T_PROCESS = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

CHECK_STEPS = 8          # harness/serve.py's
LOGIT_RTOL = 2.5e-2      # harness/serve.py's


def say(**kw):
    print(json.dumps(kw), flush=True)


# -- part ffn ----------------------------------------------------------------
def part_ffn(cfg, seed, tokens):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as ptpu
    from paddle_tpu import layers
    from paddle_tpu.ops.moe_ops import route
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    n_exp, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    dtype = cfg["torch_dtype"]

    def dense(x, rw, bias, wg, wu, wd):
        """The same selections, one expert after the other over every
        token, multiplied as the op multiplies (inputs in the weights'
        dtype, float32 sums); and the same at float32 'highest'."""
        sel, w = route(x, rw, bias, k, cfg["route_norm"], cfg["route_scale"])
        mask = jnp.zeros((x.shape[0], n_exp), jnp.float32).at[
            jnp.arange(x.shape[0])[:, None], sel].set(w)
        xs = x.astype(wg.dtype)

        def as_held(y, e):
            g, u, dn, we = e
            dot = lambda a, b: jnp.dot(     # noqa: E731
                a, b, preferred_element_type=jnp.float32)
            inner = jax.nn.silu(dot(xs, g)) * dot(xs, u)
            return y + we[:, None] * dot(inner.astype(dn.dtype), dn), None

        def exact(y, e):
            g, u, dn, we = (a.astype(jnp.float32) for a in e)
            with jax.default_matmul_precision("highest"):
                return y + we[:, None] * (
                    (jax.nn.silu(x @ g) * (x @ u)) @ dn), None
        zero = jnp.zeros_like(x)
        each = (wg, wu, wd, mask.T)
        return (jax.lax.scan(as_held, zero, each)[0],
                jax.lax.scan(exact, zero, each)[0],
                jnp.bincount(sel.reshape(-1), length=n_exp))

    with ptpu.scope_guard(ptpu.Scope()):
        scope = ptpu.global_scope()
        progs = {}
        for n in tokens:        # one program a size, the same weights by name
            main, startup = ptpu.Program(), ptpu.Program()
            main.random_seed = startup.random_seed = seed % (2 ** 31) + 1
            with ptpu.program_guard(main, startup):
                xv = layers.data("x", shape=[n, d], dtype="float32",
                                 append_batch_size=False)
                progs[n] = (main, layers.moe_ffn(
                    xv, n_exp, k, f, "l", route_norm=cfg["route_norm"],
                    route_scale=cfg["route_scale"], dtype=dtype,
                    std=cfg["initializer_range"]))
            if len(progs) == 1:
                exe = ptpu.Executor()
                exe.run(startup)
        held = [scope.find_var("l.experts.%s.w" % p)
                for p in ("gate", "up", "down")]
        rw = scope.find_var("l.router.w")
        biases = {
            "natural": np.zeros(n_exp, np.float32),
            "every_second_expert_empty":
                np.where(np.arange(n_exp) % 2, 0.0, -10.0).astype(np.float32),
            "all_tokens_on_8_experts":
                np.where(np.arange(n_exp) % 16 == 5, 10.0, 0.0).astype(
                    np.float32)}
        dense_jit = jax.jit(dense)
        rs = np.random.RandomState(seed % (2 ** 31))
        worst = 0.0
        for n in tokens:
            # RMSNorm's output: unit rows with a direction in common
            x = rs.randn(n, d).astype(np.float32) + rs.randn(1, d)
            x /= np.sqrt((x ** 2).mean(-1, keepdims=True))
            for case, bias in biases.items():
                scope.set_var("l.expert_bias", jnp.asarray(bias))
                out, counts = exe.run(progs[n][0], feed={"x": x},
                                      fetch_list=list(progs[n][1]))
                want, exact, want_counts = map(np.asarray, dense_jit(
                    jnp.asarray(x), rw, jnp.asarray(bias), *held))
                scale = float(np.abs(exact).max())
                err = float(np.abs(out - want).max()) / scale
                worst = max(worst, err)
                say(part="ffn", tokens=n, case=case,
                    experts_with_a_token=int((counts > 0).sum()),
                    busiest=int(counts.max()), pairs=int(counts.sum()),
                    counts_equal=bool((counts == want_counts).all()),
                    err_vs_same_selections_expert_by_expert=err,
                    err_vs_float32_highest=float(
                        np.abs(out - exact).max()) / scale,
                    largest_abs_output=scale)
    say(part="ffn", summary=True, worst_err_vs_same_selections=worst,
        device=str(jax.devices()[0]))


# -- part model --------------------------------------------------------------
def _moe_vars(program):
    ops = [op for op in program.global_block().ops if op.type == "moe_ffn"]
    return ([op.inputs["X"][0] for op in ops],
            [op.outputs["Counts"][0] for op in ops])


def part_model(cfg, seed, buckets, sets):
    import contextlib
    import jax
    import jax.numpy as jnp
    import paddle_tpu as ptpu
    from paddle_tpu.ops.moe_ops import route
    from paddle_tpu.serving.generation import GenerationSession
    from benchmarks import architectures
    from benchmarks.harness import lm
    arch, ref = architectures.load(cfg), architectures.reference(cfg)
    geometry = dict(cfg["deployment"]["serving"])
    k, n_exp = cfg["num_experts_per_tok"], cfg["num_experts"]
    first_expert_layer = cfg["num_dense_layers"]
    n_layers = cfg["num_hidden_layers"] - first_expert_layer
    life = contextlib.ExitStack()
    life.enter_context(lm.flags(
        generation_paged_kv=True, generation_kv_dtype=geometry["kv_dtype"],
        **cfg["flags"]))
    life.enter_context(ptpu.scope_guard(ptpu.Scope()))
    with ptpu.unique_name.guard():
        startup = arch.serve_startup(cfg, seed)
    ptpu.Executor().run(startup)
    spec = arch.serve_spec(cfg, geometry, buckets)
    sess = GenerationSession(spec)
    find = sess.scope.find_var
    weights = ref.gather_weights(find, cfg)
    routers = [(weights["l%d.router" % i], weights["l%d.expert_bias" % i])
               for i in range(first_expert_layer, cfg["num_hidden_layers"])]

    @jax.jit
    def choose(ms):
        """The op's own router over the rows the program fed it:
        (ids [layers, n, k] sorted, scores [layers, n, E])."""
        ids, scores = [], []
        for m, (rw, bias) in zip(ms, routers):
            m = m.reshape(-1, m.shape[-1])
            sel, _ = route(m, rw, bias, k, cfg["route_norm"],
                           cfg["route_scale"])
            ids.append(jnp.sort(sel, axis=-1))
            scores.append(jax.nn.sigmoid(jnp.dot(
                m.astype(jnp.float32), rw,
                precision=jax.lax.Precision.HIGHEST)))
        return jnp.stack(ids), jnp.stack(scores)

    ref_fn = jax.jit(lambda w, t, pos, forced: ref.routed(w, t, pos, cfg,
                                                          forced))
    decode_m, decode_counts = _moe_vars(spec.decode_program)
    logits_name = lm.logits_var(spec.decode_program, spec.decode_fetch)
    vocab, width = arch.vocab(cfg), buckets[-1]
    run = sess.exe.run
    tally = dict(positions=0, over_limit=0, over_limit_without_a_flip=0,
                 with_a_flip=0, flips=0, flips_beyond_5x_noise=0,
                 counts_mismatches=0, worst_free=0.0, worst_forced=0.0,
                 worst_without_a_flip=0.0, first_over=0, first_over_forced=0,
                 prompt_decisions=0, prompt_decisions_differ=0)
    by_layer = np.zeros((n_layers, 2), np.int64)

    for r in range(sets):
        rs = np.random.RandomState((seed + 7919 + 104729 * r) % (2 ** 32))
        lens = [min(b - 2, width - CHECK_STEPS - 2) for b in buckets]
        prompts = [rs.randint(2, vocab, n).astype(np.int64) for n in lens]
        slots, toks, prefill_m = [], [], []
        for p in prompts:
            tapped = []

            def tap(program, feed=None, fetch_list=None, **kw):
                extra = _moe_vars(program)[0]
                outs = run(program, feed=feed,
                           fetch_list=list(fetch_list) + extra, **kw)
                tapped.extend(outs[len(fetch_list):])
                return outs[:len(fetch_list)]
            sess.exe.run = tap
            try:
                slot, first = sess.admit(p)
            finally:
                sess.exe.run = run
            slots.append(slot)
            toks.append([first])
            prefill_m.append([np.asarray(m, np.float32)[0] for m in tapped])
        got = [[] for _ in prompts]
        chosen = [[] for _ in prompts]      # per step (ids, scores) rows
        for _ in range(CHECK_STEPS):
            prepared = sess.step_prepare()
            outs = run(spec.decode_program, feed=prepared[2],
                       fetch_list=[logits_name] + decode_m + decode_counts,
                       scope=sess.scope)
            logits = np.asarray(outs[0], np.float32)
            ids, scores = map(np.asarray, choose(
                [jnp.asarray(m) for m in outs[1:1 + n_layers]]))
            for li in range(n_layers):
                mine = np.bincount(ids[li].reshape(-1), minlength=n_exp)
                theirs = np.asarray(outs[1 + n_layers + li]).reshape(-1)
                tally["counts_mismatches"] += int((mine != theirs).any())
            out = sess.step_run(prepared)
            for i, slot in enumerate(slots):
                got[i].append(logits[slot])
                chosen[i].append((ids[:, slot], scores[:, slot]))
                toks[i].append(out[slot])
        for slot in slots:
            sess.retire(slot)

        for i, (p, n) in enumerate(zip(prompts, lens)):
            seq = np.zeros(width, np.int32)
            seq[:n] = p
            seq[n:n + CHECK_STEPS + 1] = toks[i]
            pos = np.arange(n - 1, n + CHECK_STEPS, dtype=np.int32)
            # the program's choices at the compared rows: the prefill's
            # last row, then the decode steps
            pre_ids, pre_scores = map(np.asarray, choose(
                [jnp.asarray(m[:n]) for m in prefill_m[i]]))
            rows = np.zeros(width, bool)
            rows[pos] = True
            ids = np.zeros((n_layers, width, k), np.int32)
            ids[:, n - 1] = pre_ids[:, n - 1]
            prog_scores = np.zeros((n_layers, CHECK_STEPS + 1, n_exp),
                                   np.float32)
            prog_scores[:, 0] = pre_scores[:, n - 1]
            for step, (sid, ssc) in enumerate(chosen[i]):
                ids[:, n + step] = sid
                prog_scores[:, step + 1] = ssc
            no_force = [(jnp.zeros(width, bool),
                         jnp.zeros((width, k), jnp.int32))] * n_layers
            free, ref_scores = map(np.asarray, ref_fn(
                weights, jnp.asarray(seq), jnp.asarray(pos), no_force))
            forced, _ = ref_fn(
                weights, jnp.asarray(seq), jnp.asarray(pos),
                [(jnp.asarray(rows), jnp.asarray(ids[li]))
                 for li in range(n_layers)])
            forced = np.asarray(forced)
            scale = float(np.abs(free).max())
            ref_ids = np.sort(np.argsort(-ref_scores, axis=-1)[..., :k], -1)
            # every prompt row: how often the two choose differently
            differ = (ref_ids[:, :n] != pre_ids).any(-1)      # [layers, n]
            tally["prompt_decisions"] += int(differ.size)
            tally["prompt_decisions_differ"] += int(differ.sum())
            by_layer[:, 0] += differ.shape[1]
            by_layer[:, 1] += differ.sum(1)
            for j in range(CHECK_STEPS + 1):
                at = n - 1 + j
                flips = []
                for li in range(n_layers):
                    mine, theirs = set(ids[li, at]), set(ref_ids[li, at])
                    if mine == theirs:
                        continue
                    s = ref_scores[li, at]
                    noise = float(np.abs(prog_scores[li, j] - s).max())
                    margin = float(min(s[list(theirs - mine)])
                                   - max(s[list(mine - theirs)]))
                    flips.append(dict(
                        layer=first_expert_layer + li,
                        program_took=sorted(int(e) for e in mine - theirs),
                        reference_took=sorted(int(e) for e in theirs - mine),
                        reference_margin=margin, score_noise=noise))
                    tally["flips"] += 1
                    tally["flips_beyond_5x_noise"] += int(margin > 5 * noise)
                ordered = np.sort(ref_scores[:, at], axis=-1)
                row = dict(part="model", set=r, bucket=int(buckets[i]),
                           position=int(at), flips=flips,
                           least_kth_margin=float(
                               (ordered[:, -k] - ordered[:, -k - 1]).min()))
                if j == 0:
                    first = toks[i][0]
                    row.update(
                        row="prefill",
                        first_token_rel_gap=float(
                            free[0].max() - free[0][first]) / scale,
                        first_token_rel_gap_selections_given=float(
                            forced[0].max() - forced[0][first]) / scale)
                    tally["first_over"] += int(
                        row["first_token_rel_gap"] > LOGIT_RTOL)
                    tally["first_over_forced"] += int(
                        row["first_token_rel_gap_selections_given"]
                        > LOGIT_RTOL)
                else:
                    e_free = float(np.abs(got[i][j - 1] - free[j]).max()) \
                        / scale
                    e_forced = float(np.abs(got[i][j - 1] - forced[j]).max()) \
                        / scale
                    row.update(row="decode", rel_err=e_free,
                               rel_err_selections_given=e_forced)
                    tally["positions"] += 1
                    tally["over_limit"] += int(e_free > LOGIT_RTOL)
                    tally["with_a_flip"] += int(bool(flips))
                    tally["over_limit_without_a_flip"] += int(
                        e_free > LOGIT_RTOL and not flips)
                    tally["worst_free"] = max(tally["worst_free"], e_free)
                    tally["worst_forced"] = max(tally["worst_forced"],
                                                e_forced)
                    if not flips:
                        tally["worst_without_a_flip"] = max(
                            tally["worst_without_a_flip"], e_free)
                say(**row)
    life.close()
    say(part="model", summary=True, seed=seed, sets=sets,
        buckets=list(buckets), limit=LOGIT_RTOL,
        differ_share_by_expert_layer=[
            float(b) / max(int(a), 1) for a, b in by_layer],
        device=str(jax.devices()[0]), **tally)


# -- part float32 ------------------------------------------------------------
def part_float32(cfg, seed, buckets, slots, kernel):
    """The harness's own ``Deployment`` and check, on a cut that fits in
    float32: the leading dense layer and one expert layer of each cache
    kind, everything else as published."""
    import jax
    from benchmarks.harness import common, serve
    cfg = copy.deepcopy(cfg)
    # on a TPU the executor traces under BF16_BF16_F32 unless told otherwise
    cfg["flags"]["matmul_precision"] = "highest"
    checked = common.check_kernel_compiled
    if not kernel:
        cfg["flags"]["flash_attention"] = False
        common.check_kernel_compiled = lambda *a, **kw: None
    cfg.update(torch_dtype="float32", num_hidden_layers=3,
               layer_types=["sliding_attention", "sliding_attention",
                            "full_attention"])
    geometry = cfg["deployment"]["serving"]
    bs, window = geometry["block_size"], cfg["sliding_window"]
    geometry.update(
        slots=slots, kv_dtype="float32",
        num_blocks=slots * geometry["cache_len"] // bs,
        window_num_blocks=slots * (window // bs + 2) + buckets[-1] // bs)
    env = common.Env(T_PROCESS, "routing_agreement_float32", 1, False,
                     require_tpu=False)
    problem = None
    try:
        dep = serve.Deployment({"prompt_buckets": list(buckets)}, cfg, seed,
                               env)
        report = dep.check_report
        dep.close()
    except common.BenchFailure as exc:      # the report comes first
        problem, report = str(exc), None
    finally:
        common.check_kernel_compiled = checked
    say(part="float32", summary=True, seed=seed, buckets=list(buckets),
        layer_types=cfg["layer_types"], geometry=geometry, problem=problem,
        decode_attention="decode_attention_paged (bfloat16 products)"
        if kernel else "XLA gather at the highest precision",
        report=report, limit=serve.LOGIT_RTOL,
        passes=bool(report and report["worst_rel_err"] <= serve.LOGIT_RTOL
                    and report["worst_first_token_rel_gap"]
                    <= serve.LOGIT_RTOL),
        device=str(jax.devices()[0]))


def main(argv=None):
    from benchmarks import architectures
    from benchmarks.harness import lm
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--part", required=True,
                    choices=("ffn", "model", "float32"))
    ap.add_argument("--config", default="trinity-mini-l5")
    ap.add_argument("--seed", type=int, default=2700000001)
    ap.add_argument("--buckets", default="1024,2048,4096")
    ap.add_argument("--sets", type=int, default=4,
                    help="model: prompt sets of one prompt a bucket")
    ap.add_argument("--tokens", default="64,2048",
                    help="ffn: rows of a call")
    ap.add_argument("--slots", type=int, default=8, help="float32: slots")
    ap.add_argument("--kernel", type=int, choices=(0, 1), default=1,
                    help="float32: 0 takes the XLA gather in decode")
    ap.add_argument("--tiny", action="store_true",
                    help="the architecture's CPU size (a rehearsal)")
    args = ap.parse_args(argv)
    cfg = lm.load_config(args.config)
    if args.tiny:
        cfg = architectures.load(cfg).tiny(cfg)
    buckets = tuple(int(b) for b in args.buckets.split(","))
    if args.part == "ffn":
        part_ffn(cfg, args.seed, [int(n) for n in args.tokens.split(",")])
    elif args.part == "model":
        part_model(cfg, args.seed, buckets, args.sets)
    else:
        part_float32(cfg, args.seed, buckets, args.slots, args.kernel)
    return 0


if __name__ == "__main__":
    sys.exit(main())
