"""Raw-JAX ResNet-50 train step: the framework-free upper bound.

Hand-written flax-style RN50 (bf16 activations, f32 params, momentum)
with no Program/Executor in the loop — if this matches the number of
``chip_smoke.py``'s ResNet-50 phase, the framework's step IS what XLA delivers for this model on
this chip, and the remaining MFU gap is the model's arithmetic
intensity, not the engine. See PROFILE.md round-4 cap analysis.
"""

import time
import sys

import numpy as np
import jax
import jax.numpy as jnp


def conv(x, w, stride=1, pad=None):
    kh = w.shape[2]
    p = (kh - 1) // 2 if pad is None else pad
    return jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), (stride, stride), [(p, p), (p, p)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


def bn(x, g, b, train=True):
    xf = x.astype(jnp.float32)
    m = jnp.mean(xf, axis=(0, 2, 3))
    v = jnp.mean(jnp.square(xf), axis=(0, 2, 3)) - m * m
    inv = jax.lax.rsqrt(v + 1e-5)
    a = (inv * g).reshape(1, -1, 1, 1).astype(x.dtype)
    c = (b - m * inv * g).reshape(1, -1, 1, 1).astype(x.dtype)
    return x * a + c


def bottleneck(x, p, stride):
    short = x
    if "ws" in p:
        short = bn(conv(x, p["ws"], stride, 0), p["gs"], p["bs"])
    h = jnp.maximum(bn(conv(x, p["w1"], stride, 0), p["g1"], p["b1"]), 0)
    h = jnp.maximum(bn(conv(h, p["w2"], 1, 1), p["g2"], p["b2"]), 0)
    h = bn(conv(h, p["w3"], 1, 0), p["g3"], p["b3"])
    return jnp.maximum(short + h, 0)


STAGES = [(64, 3, 1), (128, 4, 2), (256, 6, 2), (512, 3, 2)]


def init_params(rs):
    def w(*shape):
        fan = np.prod(shape[1:])
        return jnp.asarray(rs.randn(*shape) * np.sqrt(2.0 / fan),
                           jnp.float32)
    params = {"stem": {"w": w(64, 3, 7, 7), "g": jnp.ones(64),
                       "b": jnp.zeros(64)}}
    cin = 64
    for si, (ch, n, _) in enumerate(STAGES):
        blocks = []
        for bi in range(n):
            p = {"w1": w(ch, cin, 1, 1), "g1": jnp.ones(ch),
                 "b1": jnp.zeros(ch),
                 "w2": w(ch, ch, 3, 3), "g2": jnp.ones(ch),
                 "b2": jnp.zeros(ch),
                 "w3": w(ch * 4, ch, 1, 1), "g3": jnp.ones(ch * 4),
                 "b3": jnp.zeros(ch * 4)}
            if bi == 0:
                p.update({"ws": w(ch * 4, cin, 1, 1),
                          "gs": jnp.ones(ch * 4),
                          "bs": jnp.zeros(ch * 4)})
            blocks.append(p)
            cin = ch * 4
        params["s%d" % si] = blocks
    params["fc_w"] = w(1000, 2048).T / 10
    params["fc_b"] = jnp.zeros(1000)
    return params


def forward(params, img, label):
    x = img.astype(jnp.bfloat16)
    x = jnp.maximum(bn(conv(x, params["stem"]["w"], 2, 3),
                       params["stem"]["g"], params["stem"]["b"]), 0)
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, 3, 3),
                              (1, 1, 2, 2), [(0, 0), (0, 0), (1, 1),
                                             (1, 1)])
    for si, (ch, n, stride) in enumerate(STAGES):
        for bi in range(n):
            x = bottleneck(x, params["s%d" % si][bi],
                           stride if bi == 0 else 1)
    x = jnp.mean(x.astype(jnp.float32), axis=(2, 3))
    logits = x @ params["fc_w"] + params["fc_b"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, label, axis=1))


@jax.jit
def train_step(params, vel, img, label):
    loss, grads = jax.value_and_grad(forward)(params, img, label)

    def upd(p, g, v):
        nv = 0.9 * v + g
        return p - 0.1 * nv, nv
    flat_p, tree = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_v = jax.tree.leaves(vel)
    out = [upd(p, g, v) for p, g, v in zip(flat_p, flat_g, flat_v)]
    new_p = jax.tree.unflatten(tree, [o[0] for o in out])
    new_v = jax.tree.unflatten(tree, [o[1] for o in out])
    return new_p, new_v, loss


def main():
    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 256
    rs = np.random.RandomState(0)
    params = init_params(rs)
    vel = jax.tree.map(jnp.zeros_like, params)
    img = jax.device_put(jnp.asarray(rs.randn(batch, 3, 224, 224),
                                     jnp.float32))
    label = jax.device_put(jnp.asarray(
        rs.randint(0, 1000, (batch, 1)), jnp.int32))

    lowered = train_step.lower(params, vel, img, label)
    comp = lowered.compile()
    ca = comp.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca

    params, vel, loss = train_step(params, vel, img, label)
    np.asarray(loss)
    t0 = time.perf_counter()
    steps = 20
    for _ in range(steps):
        params, vel, loss = train_step(params, vel, img, label)
    lv = float(np.asarray(loss))
    dt = (time.perf_counter() - t0) / steps
    print({"raw_jax_ms_per_step": round(dt * 1e3, 1),
           "img_per_sec": round(batch / dt, 1),
           "mfu": round(batch / dt * 12.3e9 / 197e12, 4),
           "ca_gb": round(ca.get("bytes accessed", 0) / 1e9, 2),
           "ca_tflops": round(ca.get("flops", 0) / 1e12, 2),
           "loss": round(lv, 3)})


if __name__ == "__main__":
    main()
