"""Perf experiment: raw-JAX ResNet-50 train step, NCHW vs NHWC, batch sweep.

Establishes the chip's achievable ceiling outside the framework so we know
how much of the MFU gap is layout/batch vs executor overhead.
Run on the real TPU: python experiments/exp_layout.py
"""
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

# ResNet-50 config: (blocks, channels) per stage
STAGES = [(3, 64), (4, 128), (6, 256), (3, 512)]
STRIDES = []  # per-block strides, static (filled by init_params)


def init_params(rng, layout):
    STRIDES.clear()

    def conv(cin, cout, k):
        nonlocal rng
        rng, sub = jax.random.split(rng)
        w = jax.random.normal(sub, (cout, cin, k, k), jnp.float32) * 0.05
        if layout == "NHWC":
            w = jnp.transpose(w, (2, 3, 1, 0))  # HWIO
        return w

    def bn(c):
        return (jnp.ones((c,)), jnp.zeros((c,)))

    p = {"stem": (conv(3, 64, 7), bn(64))}
    cin = 64
    blocks = []
    for si, (n, ch) in enumerate(STAGES):
        for bi in range(n):
            cout = ch * 4
            stride = 2 if (si > 0 and bi == 0) else 1
            blk = {
                "c1": (conv(cin, ch, 1), bn(ch)),
                "c2": (conv(ch, ch, 3), bn(ch)),
                "c3": (conv(ch, cout, 1), bn(cout)),
            }
            if cin != cout or stride != 1:
                blk["proj"] = (conv(cin, cout, 1), bn(cout))
            blocks.append(blk)
            STRIDES.append(stride)
            cin = cout
    p["blocks"] = blocks
    rng, sub = jax.random.split(rng)
    p["fc"] = jax.random.normal(sub, (cin, 1000), jnp.float32) * 0.01
    return p


def conv_op(x, w, stride, layout, bf16):
    dn = ("NCHW", "OIHW", "NCHW") if layout == "NCHW" else ("NHWC", "HWIO", "NHWC")
    if bf16:
        x = x.astype(jnp.bfloat16)
        w = w.astype(jnp.bfloat16)
    k = w.shape[2] if layout == "NCHW" else w.shape[0]
    pad = (k - 1) // 2
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)], dimension_numbers=dn
    )


def bn_op(x, scale, bias, layout):
    x32 = x.astype(jnp.float32)
    axes = (0, 2, 3) if layout == "NCHW" else (0, 1, 2)
    shape = (1, -1, 1, 1) if layout == "NCHW" else (1, 1, 1, -1)
    m = jnp.mean(x32, axes)
    v = jnp.var(x32, axes)
    out = (x32 - m.reshape(shape)) * jax.lax.rsqrt(v.reshape(shape) + 1e-5)
    return (out * scale.reshape(shape) + bias.reshape(shape)).astype(x.dtype)


def forward(p, x, layout, bf16):
    w, (s, b) = p["stem"]
    x = jax.nn.relu(bn_op(conv_op(x, w, 2, layout, bf16), s, b, layout))
    if layout == "NCHW":
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, 3, 3), (1, 1, 2, 2), ((0, 0), (0, 0), (1, 1), (1, 1)))
    else:
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1), ((0, 0), (1, 1), (1, 1), (0, 0)))
    for bi, blk in enumerate(p["blocks"]):
        st = STRIDES[bi]
        w1, (s1, b1) = blk["c1"]
        w2, (s2, b2) = blk["c2"]
        w3, (s3, b3) = blk["c3"]
        y = jax.nn.relu(bn_op(conv_op(x, w1, 1, layout, bf16), s1, b1, layout))
        y = jax.nn.relu(bn_op(conv_op(y, w2, st, layout, bf16), s2, b2, layout))
        y = bn_op(conv_op(y, w3, 1, layout, bf16), s3, b3, layout)
        if "proj" in blk:
            wp, (sp, bp) = blk["proj"]
            x = bn_op(conv_op(x, wp, st, layout, bf16), sp, bp, layout)
        x = jax.nn.relu(x + y)
    axes = (2, 3) if layout == "NCHW" else (1, 2)
    x = jnp.mean(x.astype(jnp.float32), axes)
    return x @ p["fc"]


def loss_fn(p, x, y, layout, bf16):
    logits = forward(p, x, layout, bf16)
    return jnp.mean(-jax.nn.log_softmax(logits)[jnp.arange(x.shape[0]), y])


def bench(layout, batch, bf16=True, steps=40):
    rng = jax.random.PRNGKey(0)
    p = init_params(rng, layout)
    shape = (batch, 3, 224, 224) if layout == "NCHW" else (batch, 224, 224, 3)
    x = jnp.asarray(np.random.randn(*shape), jnp.float32)
    y = jnp.asarray(np.random.randint(0, 1000, (batch,)))

    @jax.jit
    def step(p, x, y):
        g = jax.grad(lambda p: loss_fn(p, x, y, layout, bf16))(p)
        return jax.tree.map(lambda a, b: a - 0.01 * b, p, g)

    p = step(p, x, y)  # compile + 1
    jax.block_until_ready(p)
    t0 = time.perf_counter()
    for _ in range(steps):
        p = step(p, x, y)
    jax.block_until_ready(p)
    dt = (time.perf_counter() - t0) / steps
    imgs = batch / dt
    # bench.py accounting: fwd = 4.1 GMACs = 8.2 GFLOPs (2 FLOPs/MAC),
    # train = fwd + bwd ~= 3x fwd
    mfu = (3 * 8.2e9 * batch / dt) / 197e12
    print(f"{layout} bs={batch} bf16={bf16}: {dt*1e3:.1f} ms/step, "
          f"{imgs:.0f} img/s, MFU={mfu*100:.1f}%", flush=True)
    return imgs


if __name__ == "__main__":
    for layout in ("NCHW", "NHWC"):
        for batch in (128, 256):
            bench(layout, batch)
