"""RNG ops (reference gaussian_random_op.cc / uniform_random_op.cc).

TPU-first: stateless threaded PRNG — the executor splits the scope-held key
per op call (reference used per-device curand generators, ``paddle/platform``
dynload curand).
"""

import jax
import jax.numpy as jnp

from ..core.registry import register_op
from ..core.framework import convert_dtype


def decoding_key(seed, position):
    """THE decode-side key schedule: ``fold_in(PRNGKey(seed), position)``.

    ``position`` is the 0-based sequence index of the token being
    generated (the prompt occupies ``[0, n)``, so the first sampled
    token of an n-token prompt uses position ``n``). Counter-based
    keying is what makes stochastic decode replayable: the key for
    position *i* depends only on ``(seed, i)`` — never on which
    session, process, or fleet member runs the step, nor on how many
    RNG calls happened before it. A replay that re-prefills an
    (n+k)-token journal and resumes at position n+k derives exactly
    the key the fault-free run used.

    Every decode-side sampling site (the ``decode_sample`` /
    ``decode_verify`` ops, the ``dynamic_beam_search`` sample mode)
    MUST derive keys through this helper — serving code never touches
    ``jax.random`` directly (grep-linted in tests/test_decoding.py).
    Works on traced values: ``seed``/``position`` may be scalars or
    vmapped array elements.
    """
    return jax.random.fold_in(
        jax.random.PRNGKey(jnp.asarray(seed, jnp.uint32)),
        jnp.asarray(position, jnp.uint32))


@register_op("gaussian_random", needs_rng=True, skip_eval_shape=True)
def _gaussian_random(ctx):
    shape = tuple(ctx.attr("shape"))
    dtype = convert_dtype(ctx.attr("dtype", "float32"))
    mean = ctx.attr("mean", 0.0)
    std = ctx.attr("std", 1.0)
    z = jax.random.normal(ctx.rng_key, shape, dtype=dtype)
    if ctx.attr("clip"):        # in deviations: a draw past it lies on it
        z = jnp.clip(z, -ctx.attr("clip"), ctx.attr("clip"))
    return {"Out": mean + std * z}


@register_op("uniform_random", needs_rng=True, skip_eval_shape=True)
def _uniform_random(ctx):
    shape = tuple(ctx.attr("shape"))
    dtype = convert_dtype(ctx.attr("dtype", "float32"))
    lo = ctx.attr("min", -1.0)
    hi = ctx.attr("max", 1.0)
    return {"Out": jax.random.uniform(ctx.rng_key, shape, dtype=dtype,
                                      minval=lo, maxval=hi)}


@register_op("randint", needs_rng=True, skip_eval_shape=True)
def _randint(ctx):
    shape = tuple(ctx.attr("shape"))
    return {"Out": jax.random.randint(ctx.rng_key, shape,
                                      ctx.attr("low", 0), ctx.attr("high"),
                                      dtype=jnp.int32)}


@register_op("sampling_id", needs_rng=True)
def _sampling_id(ctx):
    """Sample a column index per row from a probability matrix (reference
    SamplingIdLayer)."""
    x = ctx.input("X")
    return {"Out": jax.random.categorical(ctx.rng_key,
                                          jnp.log(jnp.clip(x, 1e-20, None)),
                                          axis=-1).astype(jnp.int32)}
