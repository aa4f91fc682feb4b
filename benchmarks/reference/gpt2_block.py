"""Plain reference of the GPT-2 block LM (Radford et al. 2019; the block of
Cerebras-GPT, arXiv:2304.03208): pre-norm, LayerNorm, learned positions,
full multi-head causal attention, a 4x GELU feed-forward, a final LayerNorm
and an LM head. Like every reference it takes the configuration dict
(``gather_weights(find_var, cfg)``, ``logits_at(w, tokens, positions,
cfg)``, ``loss(w, tokens, labels, cfg)``) and reads its own sizes from it:
the depth, the heads and the LayerNorm epsilon. Straight ``jax.numpy`` in
float32 under ``jax.default_matmul_precision("highest")``: no kernels, no
cache, no batching, one sequence at a time.

It is fed the program's own weights by the names the program gives them
(``weight_names``), so it checks the program's arithmetic and not its
initializer. Departures from the published model that the program makes,
and this file follows so that the two can agree (each is listed in the
configuration files): the LM head is a matrix of its own (``lm_head.w``),
not the transposed token embedding; the attention projections have no bias;
GELU is the tanh approximation.
"""

import jax
import jax.numpy as jnp


def weight_names(n_layer):
    """The program's parameter names, in the reference's own terms. The
    program numbers LayerNorms in build order: two per block, then the
    final one; ``w_0`` is the scale and ``w_1`` the bias."""
    names = {"tok": "tok_embedding", "pos": "pos_encoding_0.w_0",
             "ln_f.g": "layer_norm_%d.w_0" % (2 * n_layer),
             "ln_f.b": "layer_norm_%d.w_1" % (2 * n_layer),
             "head": "lm_head.w"}
    for i in range(n_layer):
        p = "h%d." % i
        names.update({
            p + "ln1.g": "layer_norm_%d.w_0" % (2 * i),
            p + "ln1.b": "layer_norm_%d.w_1" % (2 * i),
            p + "q": "mha_%d.qkv_q.w" % i, p + "k": "mha_%d.qkv_k.w" % i,
            p + "v": "mha_%d.qkv_v.w" % i, p + "o": "mha_%d.o.w" % i,
            p + "ln2.g": "layer_norm_%d.w_0" % (2 * i + 1),
            p + "ln2.b": "layer_norm_%d.w_1" % (2 * i + 1),
            p + "fc1.w": "enc_%d.ffn1.w" % i, p + "fc1.b": "enc_%d.ffn1.b" % i,
            p + "fc2.w": "enc_%d.ffn2.w" % i, p + "fc2.b": "enc_%d.ffn2.b" % i,
        })
    return names


def gather_weights(find_var, cfg):
    """{reference name: array} from the program's scope (``find_var`` is
    ``scope.find_var``). No copy: the arrays are the program's own."""
    return {k: find_var(v) for k, v in weight_names(cfg["n_layer"]).items()}


def _layer_norm(x, g, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


def hidden(w, tokens, cfg):
    """tokens [T] -> final hidden states [T, d], after the last LayerNorm."""
    n_layer, n_head = cfg["n_layer"], cfg["n_head"]
    eps = cfg["layer_norm_epsilon"]
    t = tokens.shape[0]
    x = w["tok"][tokens] + w["pos"][:t]
    d = x.shape[-1]
    hd = d // n_head
    causal = jnp.tril(jnp.ones((t, t), bool))
    for i in range(n_layer):
        p = "h%d." % i
        h = _layer_norm(x, w[p + "ln1.g"], w[p + "ln1.b"], eps)
        q = (h @ w[p + "q"]).reshape(t, n_head, hd)
        k = (h @ w[p + "k"]).reshape(t, n_head, hd)
        v = (h @ w[p + "v"]).reshape(t, n_head, hd)
        s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(float(hd))
        s = jnp.where(causal[None], s, -jnp.inf)
        a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
        x = x + a.reshape(t, d) @ w[p + "o"]
        h = _layer_norm(x, w[p + "ln2.g"], w[p + "ln2.b"], eps)
        h = jax.nn.gelu(h @ w[p + "fc1.w"] + w[p + "fc1.b"], approximate=True)
        x = x + h @ w[p + "fc2.w"] + w[p + "fc2.b"]
    return _layer_norm(x, w["ln_f.g"], w["ln_f.b"], eps)


def logits_at(w, tokens, positions, cfg):
    """Logits [len(positions), V] of one sequence at the given positions."""
    with jax.default_matmul_precision("highest"):
        w = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
        return hidden(w, tokens, cfg)[positions] @ w["head"]


def loss(w, tokens, labels, cfg):
    """Mean next-token cross-entropy of one sequence (labels [T])."""
    with jax.default_matmul_precision("highest"):
        w = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
        logits = hidden(w, tokens, cfg) @ w["head"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
