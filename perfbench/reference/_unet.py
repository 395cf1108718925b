"""Plain U-Net retraining reference: forward, loss, gradients, Adam.

Straightforward ``jax.numpy`` / ``jax.lax`` in float32 with every matrix
product at ``Precision.HIGHEST``; no kernels, no mixed precision, no
batching tricks. It imports nothing of the program and takes nothing the
program made: weights come from :func:`init`, data from the benchmark's own
generator. Parameters are one flat dict keyed by the names the program's
checkpoint uses on disk ("Down_0/DoubleConv_0/Conv_1/kernel"), so the driver
can hand the same seeded weights to the program as a step-0 checkpoint.

Architecture (Ronneberger et al., arXiv:1505.04597, in the padded,
batch-normed form of the reference repo's ``UNet(3, 1)``): DoubleConv =
(3x3 SAME conv without bias -> batch norm -> ReLU) x 2; four 2x2 max-pool
encoder levels; a decoder of either bilinear ``align_corners`` upsampling
(``bilinear``: ladder 64-128-256-512-512, DoubleConv mid width halved) or
2x2 stride-2 transposed convolutions (ladder to 1024); skip concatenation
as [skip, upsampled]; a 1x1 head with bias. Departures, all the program's
documented semantics: NHWC layout; batch-norm running statistics use the
biased batch variance with momentum 0.9 and eps 1e-5.

``precision`` selects the arithmetic of every convolution's operands:
"f32" is the reference proper; "bf16" is what the configurations state
(sanity: it must pass the comparison); "int8" is the control, the nearest
precision below bf16 and the one a v5e (393 TOP/s int8) tempts: symmetric
per-tensor int8 of both operands in the forward and both backward
convolutions.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
BN_MOMENTUM = 0.9
BN_EPS = 1e-5
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
_DN = ("NHWC", "HWIO", "NHWC")


# -- structure ---------------------------------------------------------------

def _double_conv_shapes(prefix, cin, mid, cout):
    p = prefix + "DoubleConv_0/"
    return {
        p + "Conv_0/kernel": (3, 3, cin, mid),
        p + "BatchNorm_0/scale": (mid,), p + "BatchNorm_0/bias": (mid,),
        p + "Conv_1/kernel": (3, 3, mid, cout),
        p + "BatchNorm_1/scale": (cout,), p + "BatchNorm_1/bias": (cout,),
    }


def param_shapes(cfg: dict) -> dict:
    """Flat name -> shape of every trainable leaf for a configuration's
    ``model`` group (in_channels, num_classes, base_features, bilinear)."""
    f, bil = cfg["base_features"], cfg["bilinear"]
    factor = 2 if bil else 1
    enc = [f, 2 * f, 4 * f, 8 * f, 16 * f // factor]
    shapes = _double_conv_shapes("", cfg["in_channels"], f, f)
    for i in range(4):
        shapes.update(_double_conv_shapes(f"Down_{i}/", enc[i], enc[i + 1],
                                          enc[i + 1]))
    x_ch = enc[4]
    for i, out in enumerate([8 * f // factor, 4 * f // factor,
                             2 * f // factor, f]):
        skip = enc[3 - i]
        if bil:
            cin, mid = x_ch + skip, (x_ch + skip) // 2
        else:
            shapes[f"Up_{i}/ConvTranspose_0/kernel"] = (2, 2, x_ch, x_ch // 2)
            shapes[f"Up_{i}/ConvTranspose_0/bias"] = (x_ch // 2,)
            cin, mid = x_ch // 2 + skip, out
        shapes.update(_double_conv_shapes(f"Up_{i}/", cin, mid, out))
        x_ch = out
    shapes["Conv_0/kernel"] = (1, 1, f, cfg["num_classes"])
    shapes["Conv_0/bias"] = (cfg["num_classes"],)
    return shapes


def stat_shapes(cfg: dict) -> dict:
    """Flat name -> shape of the batch-norm running statistics."""
    out = {}
    for name, shape in param_shapes(cfg).items():
        if name.endswith("/scale"):
            base = name[: -len("scale")]
            out[base + "mean"] = shape
            out[base + "var"] = shape
    return out


def init(cfg: dict, seed: int):
    """Seeded weights, made on the device in one jitted call: conv kernels
    and the head bias U(+-1/sqrt(fan_in)) (the family torch's Conv2d
    defaults to), transposed-conv kernel and bias U(+-1/sqrt(4 * out_ch)),
    norm scale 1 and bias 0, running mean 0 and variance 1."""
    shapes = param_shapes(cfg)
    names = sorted(shapes)

    @jax.jit
    def make(key):
        params = {}
        for i, name in enumerate(names):
            shape = shapes[name]
            k = jax.random.fold_in(key, i)
            if name.endswith("/scale"):
                params[name] = jnp.ones(shape, jnp.float32)
            elif "BatchNorm" in name:
                params[name] = jnp.zeros(shape, jnp.float32)
            else:
                if "ConvTranspose" in name:
                    fan = 4 * shapes[name.rsplit("/", 1)[0] + "/bias"][0]
                elif name.endswith("/bias"):
                    fan = shapes[name.rsplit("/", 1)[0] + "/kernel"][2]
                else:
                    fan = shape[0] * shape[1] * shape[2]
                bound = 1.0 / np.sqrt(fan)
                params[name] = jax.random.uniform(
                    k, shape, jnp.float32, -bound, bound)
        return params

    params = make(jax.random.key(seed % (2 ** 31 - 1)))
    stats = {n: (jnp.ones if n.endswith("var") else jnp.zeros)(s, jnp.float32)
             for n, s in stat_shapes(cfg).items()}
    return params, stats


# -- arithmetic --------------------------------------------------------------

def _int8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.round(x / scale).clip(-127, 127) * scale


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


_ROUND = {"f32": lambda x: x, "bf16": _bf16, "int8": _int8}


def _conv_f32(x, w, padding):
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), padding, dimension_numbers=_DN, precision=HIGHEST)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _conv(x, w, padding, precision):
    r = _ROUND[precision]
    return _conv_f32(r(x), r(w), padding)


def _conv_fwd(x, w, padding, precision):
    r = _ROUND[precision]
    rx, rw = r(x), r(w)
    return _conv_f32(rx, rw, padding), (rx, rw)


def _conv_bwd(padding, precision, res, g):
    rx, rw = res
    _, vjp = jax.vjp(lambda a, b: _conv_f32(a, b, padding), rx, rw)
    return vjp(_ROUND[precision](g))


_conv.defvjp(_conv_fwd, _conv_bwd)


def _batch_norm(x, scale, bias, mean, var):
    return (x - mean) * jax.lax.rsqrt(var + BN_EPS) * scale + bias


def _double_conv(p, stats, new_stats, prefix, x, train, precision):
    for j in (0, 1):
        base = f"{prefix}DoubleConv_0/"
        x = _conv(x, p[f"{base}Conv_{j}/kernel"], "SAME", precision)
        bn = f"{base}BatchNorm_{j}/"
        if train:
            mean = jnp.mean(x, axis=(0, 1, 2))
            var = jnp.mean(jnp.square(x), axis=(0, 1, 2)) - jnp.square(mean)
            var = jnp.maximum(var, 0.0)
            for key, val in (("mean", mean), ("var", var)):
                new_stats[bn + key] = (BN_MOMENTUM * stats[bn + key]
                                       + (1 - BN_MOMENTUM) * val)
        else:
            mean, var = stats[bn + "mean"], stats[bn + "var"]
        x = _batch_norm(x, p[bn + "scale"], p[bn + "bias"], mean, var)
        x = jnp.maximum(x, 0.0)
    return x


def _max_pool(x):
    b, h, w, c = x.shape
    return jnp.max(x.reshape(b, h // 2, 2, w // 2, 2, c), axis=(2, 4))


def _interp_matrix(out, inp):
    pos = np.arange(out) * ((inp - 1) / (out - 1)) if out > 1 and inp > 1 \
        else np.zeros(out)
    i0 = np.clip(np.floor(pos).astype(int), 0, inp - 1)
    i1 = np.minimum(i0 + 1, inp - 1)
    m = np.zeros((out, inp), np.float32)
    np.add.at(m, (np.arange(out), i0), 1.0 - (pos - i0))
    np.add.at(m, (np.arange(out), i1), pos - i0)
    return jnp.asarray(m)


def _upsample_bilinear(x, h, w):
    """Bilinear resize on the ``align_corners=True`` grid."""
    x = jnp.einsum("Hh,bhwc->bHwc", _interp_matrix(h, x.shape[1]), x,
                   precision=HIGHEST)
    return jnp.einsum("Ww,bhwc->bhWc", _interp_matrix(w, x.shape[2]), x,
                      precision=HIGHEST)


def _conv_transpose_2x2(x, w, bias, precision):
    """out[b, 2i+di, 2j+dj, o] = sum_c x[b,i,j,c] * w[1-di, 1-dj, c, o]
    + bias[o]: a 2x2 stride-2 transposed convolution written as four 1x1
    convolutions, in the kernel orientation the checkpoint stores."""
    b, h, wd, _ = x.shape
    rows = []
    for di in (0, 1):
        cols = [_conv(x, w[1 - di:2 - di, 1 - dj:2 - dj], "VALID", precision)
                for dj in (0, 1)]
        rows.append(jnp.stack(cols, axis=3))          # b h w 2 o
    y = jnp.stack(rows, axis=2)                       # b h 2 w 2 o
    return y.reshape(b, 2 * h, 2 * wd, -1) + bias


def forward(cfg, params, stats, x, train, precision="f32", remat=False):
    """Logits [B,H,W,num_classes] and the updated running statistics."""
    new_stats = dict(stats)

    def block(prefix, x):
        def run(p, s, x):
            ns = {}
            y = _double_conv(p, s, ns, prefix, x, train, precision)
            return y, ns
        if remat:
            run = jax.checkpoint(run)
        keys = [k for k in params if k.startswith(prefix + "DoubleConv_0/")]
        skeys = [k for k in stats if k.startswith(prefix + "DoubleConv_0/")]
        y, ns = run({k: params[k] for k in keys},
                    {k: stats[k] for k in skeys}, x)
        new_stats.update(ns)
        return y

    skips = [block("", x)]
    for i in range(4):
        skips.append(block(f"Down_{i}/", _max_pool(skips[-1])))
    y = skips.pop()
    for i in range(4):
        skip = skips.pop()
        if cfg["bilinear"]:
            y = _upsample_bilinear(y, skip.shape[1], skip.shape[2])
        else:
            y = _conv_transpose_2x2(
                y, params[f"Up_{i}/ConvTranspose_0/kernel"],
                params[f"Up_{i}/ConvTranspose_0/bias"], precision)
        y = block(f"Up_{i}/", jnp.concatenate([skip, y], axis=-1))
    logits = _conv(y, params["Conv_0/kernel"], "VALID", precision)
    return logits + params["Conv_0/bias"], new_stats


def bce_with_logits(logits, labels):
    """Mean binary cross-entropy on logits, the numerically stable form."""
    return jnp.mean(jnp.maximum(logits, 0.0) - logits * labels
                    + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def adam_init(params):
    zeros = {k: jnp.zeros_like(v) for k, v in params.items()}
    return {"mu": zeros, "nu": dict(zeros), "count": jnp.zeros((), jnp.int32)}


def train_step(cfg, lr, params, opt, stats, x, y, precision="f32",
               remat=False):
    """One optimiser step: (params, opt, stats, loss) after Adam."""

    def loss_of(p):
        logits, new_stats = forward(cfg, p, stats, x, True, precision, remat)
        return bce_with_logits(logits, y), new_stats

    (loss, new_stats), grads = jax.value_and_grad(loss_of, has_aux=True)(params)
    count = opt["count"] + 1
    t = count.astype(jnp.float32)
    mu = {k: ADAM_B1 * opt["mu"][k] + (1 - ADAM_B1) * grads[k] for k in grads}
    nu = {k: ADAM_B2 * opt["nu"][k] + (1 - ADAM_B2) * jnp.square(grads[k])
          for k in grads}
    c1, c2 = 1 - ADAM_B1 ** t, 1 - ADAM_B2 ** t
    new_params = {
        k: params[k] - lr * (mu[k] / c1) / (jnp.sqrt(nu[k] / c2) + ADAM_EPS)
        for k in params}
    return new_params, {"mu": mu, "nu": nu, "count": count}, new_stats, loss
