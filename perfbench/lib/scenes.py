"""Seeded actuator scenes: the benchmark's own traffic generator.

One jitted call renders ``n`` image/mask pairs on the device from ``seed``:
a curved actuator band (between two vertical offsets of a circular arc,
shaded across its thickness) over a colour-gradient background with
speckle and up to three distractor discs, with its exact mask. The scene
family is the one the program's ``training/synthetic.render_scene`` draws
(copied in spirit, vectorised; the original stays with the program), so a
retraining job sees what it sees in deployment. The same seed gives the
same bytes; every seed gives the same sizes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _scene(key, h: int, w: int):
    k = jax.random.split(key, 16)
    u = lambda i, lo, hi, shape=(): jax.random.uniform(  # noqa: E731
        k[i], shape, jnp.float32, lo, hi)
    uu = jnp.arange(w, dtype=jnp.float32)[None, :]
    vv = jnp.arange(h, dtype=jnp.float32)[:, None]
    img = (u(0, 40, 160, (3,)) + u(1, -40, 40, (3,)) * (uu / w)[..., None]
           + u(2, -40, 40, (3,)) * (vv / h)[..., None])
    img = img + 8.0 * jax.random.normal(k[3], (h, w, 3), jnp.float32)
    n_blobs = jax.random.randint(k[4], (), 0, 4)
    bx, by = u(5, 0, w, (3,)), u(6, 0, h, (3,))
    br, bc = u(7, 10, 60, (3,)) * (w / 640.0), u(8, 0, 255, (3, 3))
    for i in range(3):
        blob = ((uu - bx[i]) ** 2 + (vv - by[i]) ** 2 < br[i] ** 2) \
            & (i < n_blobs)
        img = jnp.where(blob[..., None], bc[i], img)
    r_px, cx = u(9, 0.5, 2.5) * w, u(10, 0.3 * w, 0.7 * w)
    v_apex, thick = u(11, 0.35, 0.85) * h, u(12, 0.12, 0.3) * h
    half_span = jnp.minimum(u(13, 0.25, 0.45) * w, 0.95 * r_px)
    v_edge = (v_apex - r_px) + jnp.sqrt(
        jnp.maximum(r_px ** 2 - (uu - cx) ** 2, 0.0))
    mask = (jnp.abs(uu - cx) <= half_span) & (vv <= v_edge) \
        & (vv >= v_edge - thick)
    shade = 1.0 - 0.4 * jnp.clip((v_edge - vv) / thick, 0.0, 1.0)
    img = jnp.where(mask[..., None], u(14, 0, 255, (3,)) * shade[..., None],
                    img)
    return (jnp.clip(img, 0, 255).astype(jnp.uint8),
            (mask.astype(jnp.uint8) * 255)[..., None])


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _render(key, n: int, h: int, w: int):
    return jax.lax.map(lambda k: _scene(k, h, w), jax.random.split(key, n),
                       batch_size=64)


def generate(seed: int, n: int, h: int, w: int):
    """(images u8 [n,h,w,3] RGB, masks u8 [n,h,w,1] coded 0/255) on the
    host. ``seed`` may be any non-negative whole number."""
    key = jax.random.fold_in(jax.random.key(seed % (2 ** 31 - 1)),
                             seed // (2 ** 31 - 1))
    imgs, masks = jax.device_get(_render(key, n, h, w))
    return np.asarray(imgs), np.asarray(masks)
