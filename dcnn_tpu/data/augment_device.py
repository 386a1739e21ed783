"""On-device (jittable) data augmentations.

Device twins of the 9 host augmentations in ``augment.py`` (reference
``include/data_augmentation/augmentation.hpp:17-114``): Brightness, Contrast,
Cutout, GaussianNoise, HorizontalFlip, VerticalFlip, Normalization,
RandomCrop, Rotation — re-designed for the TPU input path instead of
translated: each op is a pure function ``(batch, key) -> batch`` traced into
the training step itself, so augmentation runs on device at HBM bandwidth
with zero host work and zero H2D traffic (the reference augments on the host
CPU per batch, ``src/data_augmentation/augmentation.cpp``).

Per-sample "apply with probability p" masks use the step's PRNG key; every op
derives its own subkey via ``fold_in`` of a static op index, so adding or
reordering ops changes the stream deterministically, and the same (key, op
list) always produces the same batch — reproducible augmentation, which the
reference's global RNG cannot guarantee under threading.

All ops are shape-polymorphic over NCHW/NHWC (set at builder construction)
and compile into the surrounding jit: no data-dependent shapes, no host
callbacks. Rotation uses a bilinear ``map_coordinates`` gather (order=1,
nearest edge handling) — the jittable analog of the host path's
``scipy.ndimage.rotate``.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

DeviceBatchFn = Callable[[jax.Array, jax.Array], jax.Array]


def _hw_axes(data_format: str) -> Tuple[int, int]:
    return (2, 3) if data_format == "NCHW" else (1, 2)


def _per_sample_mask(key: jax.Array, n: int, p: float) -> jax.Array:
    return jax.random.uniform(key, (n,)) < p


def _bshape(x: jax.Array) -> Tuple[int, ...]:
    """[N, 1, 1, ...] broadcast shape for per-sample scalars."""
    return (x.shape[0],) + (1,) * (x.ndim - 1)


def brightness(delta: float = 0.2, p: float = 0.5) -> DeviceBatchFn:
    """Additive brightness jitter in [-delta, delta] (host twin: augment.py)."""
    def fn(x, key):
        km, ks = jax.random.split(key)
        m = _per_sample_mask(km, x.shape[0], p)
        shifts = jax.random.uniform(ks, (x.shape[0],), x.dtype, -delta, delta)
        shifts = jnp.where(m, shifts, 0).reshape(_bshape(x))
        return x + shifts
    return fn


def contrast(lower: float = 0.8, upper: float = 1.2,
             p: float = 0.5) -> DeviceBatchFn:
    """Scale around the per-image mean by a factor in [lower, upper]."""
    def fn(x, key):
        km, ks = jax.random.split(key)
        m = _per_sample_mask(km, x.shape[0], p)
        f = jax.random.uniform(ks, (x.shape[0],), x.dtype, lower, upper)
        f = jnp.where(m, f, 1).reshape(_bshape(x))
        mean = x.mean(axis=tuple(range(1, x.ndim)), keepdims=True)
        return (x - mean) * f + mean
    return fn


def cutout(size: int = 8, p: float = 0.5,
           data_format: str = "NHWC") -> DeviceBatchFn:
    """Zero a random size×size square per image.

    The square is expressed as a broadcasted-iota box mask (start <= iota <
    end per axis) — static shapes, so it fuses into the surrounding step."""
    ha, wa = _hw_axes(data_format)

    def fn(x, key):
        n = x.shape[0]
        h, w = x.shape[ha], x.shape[wa]
        km, ky, kx = jax.random.split(key, 3)
        m = _per_sample_mask(km, n, p)
        cy = jax.random.randint(ky, (n,), 0, h)
        cx = jax.random.randint(kx, (n,), 0, w)
        y0, y1 = cy - size // 2, cy + size // 2
        x0, x1 = cx - size // 2, cx + size // 2
        iy = jnp.arange(h)
        ix = jnp.arange(w)
        in_y = (iy[None, :] >= y0[:, None]) & (iy[None, :] < y1[:, None])  # [N, H]
        in_x = (ix[None, :] >= x0[:, None]) & (ix[None, :] < x1[:, None])  # [N, W]
        box = in_y[:, :, None] & in_x[:, None, :] & m[:, None, None]       # [N, H, W]
        box = jnp.expand_dims(box, axis=1 if data_format == "NCHW" else 3)
        return jnp.where(box, jnp.zeros((), x.dtype), x)
    return fn


def gaussian_noise(std: float = 0.05, p: float = 0.5) -> DeviceBatchFn:
    def fn(x, key):
        km, kn = jax.random.split(key)
        m = _per_sample_mask(km, x.shape[0], p).reshape(_bshape(x))
        noise = std * jax.random.normal(kn, x.shape, x.dtype)
        return x + jnp.where(m, noise, 0)
    return fn


def horizontal_flip(p: float = 0.5, data_format: str = "NHWC") -> DeviceBatchFn:
    _, wa = _hw_axes(data_format)

    def fn(x, key):
        m = _per_sample_mask(key, x.shape[0], p).reshape(_bshape(x))
        return jnp.where(m, jnp.flip(x, axis=wa), x)
    return fn


def vertical_flip(p: float = 0.5, data_format: str = "NHWC") -> DeviceBatchFn:
    ha, _ = _hw_axes(data_format)

    def fn(x, key):
        m = _per_sample_mask(key, x.shape[0], p).reshape(_bshape(x))
        return jnp.where(m, jnp.flip(x, axis=ha), x)
    return fn


def normalization(mean: Sequence[float], std: Sequence[float],
                  data_format: str = "NHWC") -> DeviceBatchFn:
    """Per-channel (x-mean)/std (deterministic; always applied)."""
    def fn(x, key):
        mean_a = jnp.asarray(mean, x.dtype)
        std_a = jnp.asarray(std, x.dtype)
        if data_format == "NCHW":
            return (x - mean_a.reshape(1, -1, 1, 1)) / std_a.reshape(1, -1, 1, 1)
        return (x - mean_a) / std_a
    return fn


def _shift(x: jax.Array, d: jax.Array, axis: int, reach: int) -> jax.Array:
    """``out[n, .., i, ..] = x[n, .., i + d[n], ..]`` along ``axis``, zero
    where ``i + d[n]`` falls outside; ``|d[n]| <= reach``. Bit-exact, and
    one bulk operation over the batch, never a copy per image.

    Floats go through a batched product with the 0/1 selection matrix
    ``S[n, i, k] = (k == i + d[n])``: each output element is one input
    element times 1 plus zeros, accumulated in float32. bfloat16 operands
    are exact at default precision; other floats are multiplied as float32
    at ``Precision.HIGHEST`` (the TPU's default would round them to
    bfloat16). Integers take the ``2·reach+1`` static shifts, the one for
    each image chosen by ``jnp.where``."""
    size = x.shape[axis]
    if not jnp.issubdtype(x.dtype, jnp.floating):
        zero = jnp.zeros((), x.dtype)
        out = jnp.zeros_like(x)
        for s in range(-reach, reach + 1):
            cfg = [(0, 0, 0)] * x.ndim
            cfg[axis] = (-s, s, 0)   # drop s leading rows, append s zeros
            pick = (d == s).reshape(_bshape(x))
            out = jnp.where(pick, jax.lax.pad(x, zero, cfg), out)
        return out
    bf16 = x.dtype == jnp.bfloat16
    cdt = x.dtype if bf16 else jnp.float32
    i = jnp.arange(size)
    sel = (i[None, None, :] == i[None, :, None] + d[:, None, None]).astype(cdt)
    src = "nabcdefgh"[:x.ndim]
    k = src[axis]
    out = jnp.einsum(
        f"nz{k},{src}->{src.replace(k, 'z')}", sel, x.astype(cdt),
        precision=None if bf16 else jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    return out.astype(x.dtype)


def random_crop(padding: int = 4, p: float = 1.0,
                data_format: str = "NHWC") -> DeviceBatchFn:
    """Zero-pad by ``padding`` then crop back at a per-image random offset:
    a shift of each image by ``offset - padding`` rows and columns with
    zeros moving in (:func:`_shift`), so neither the padded batch nor a
    per-image slice is ever made (XLA:TPU ran the vmapped ``dynamic_slice``
    this replaces as a loop of one copy per image). The result is bit-exact
    for finite input. A NaN or infinity spreads along its row and column
    in the floating-point path (0 · NaN); callers crop decoded pixels."""
    ha, wa = _hw_axes(data_format)

    def fn(x, key):
        n = x.shape[0]
        km, ky, kx = jax.random.split(key, 3)
        m = _per_sample_mask(km, n, p)
        oy = jnp.where(m, jax.random.randint(ky, (n,), 0, 2 * padding + 1), padding)
        ox = jnp.where(m, jax.random.randint(kx, (n,), 0, 2 * padding + 1), padding)
        x = _shift(x, oy - padding, ha, padding)
        return _shift(x, ox - padding, wa, padding)
    return fn


def rotation(max_degrees: float = 15.0, p: float = 0.5,
             data_format: str = "NHWC") -> DeviceBatchFn:
    """Rotate each image by a uniform angle in [-max_degrees, max_degrees]
    about its center: bilinear resample via ``map_coordinates`` (order=1,
    edge-clamped) — the jittable twin of the host path's ndimage.rotate."""
    ha, wa = _hw_axes(data_format)

    def fn(x, key):
        n = x.shape[0]
        h, w = x.shape[ha], x.shape[wa]
        km, ka = jax.random.split(key)
        m = _per_sample_mask(km, n, p)
        deg = jax.random.uniform(ka, (n,), jnp.float32,
                                 -max_degrees, max_degrees)
        theta = jnp.where(m, deg, 0.0) * (jnp.pi / 180.0)
        cy, cx = (h - 1) / 2.0, (w - 1) / 2.0
        yy, xx = jnp.meshgrid(jnp.arange(h, dtype=jnp.float32),
                              jnp.arange(w, dtype=jnp.float32), indexing="ij")

        def rot_one(img, th):
            # inverse map: output (y, x) samples input at R(-th) (y-c, x-c) + c
            cos, sin = jnp.cos(th), jnp.sin(th)
            sy = cos * (yy - cy) - sin * (xx - cx) + cy
            sx = sin * (yy - cy) + cos * (xx - cx) + cx
            # clamp to edges (host twin uses mode="nearest")
            sy = jnp.clip(sy, 0.0, h - 1)
            sx = jnp.clip(sx, 0.0, w - 1)

            def plane(p2d):
                return jax.scipy.ndimage.map_coordinates(
                    p2d.astype(jnp.float32), [sy, sx], order=1,
                    mode="nearest").astype(img.dtype)

            if data_format == "NCHW":    # img: [C, H, W]
                return jax.vmap(plane)(img)
            return jnp.moveaxis(jax.vmap(plane)(jnp.moveaxis(img, 2, 0)), 0, 2)

        return jax.vmap(rot_one)(x, theta)
    return fn


class DeviceAugment:
    """Ordered jittable augmentation pipeline: ``aug(batch, key)`` applies
    every op with an op-indexed subkey. Device twin of the host
    ``AugmentationStrategy`` (augment.py; reference augmentation.hpp:51)."""

    def __init__(self, ops: Optional[List[DeviceBatchFn]] = None):
        self.ops: List[DeviceBatchFn] = list(ops or [])

    def add(self, op: DeviceBatchFn) -> "DeviceAugment":
        self.ops.append(op)
        return self

    def __call__(self, batch: jax.Array, key: jax.Array) -> jax.Array:
        for i, op in enumerate(self.ops):
            batch = op(batch, jax.random.fold_in(key, i))
        return batch


class DeviceAugmentBuilder:
    """Fluent construction, mirroring the host ``AugmentationBuilder``
    (augment.py; reference augmentation.hpp:114) so trainer configs can swap
    host-side for on-device augmentation without rewriting the recipe."""

    def __init__(self, data_format: str = "NHWC"):
        self._aug = DeviceAugment()
        self.data_format = data_format

    def brightness(self, delta: float = 0.2, p: float = 0.5):
        self._aug.add(brightness(delta, p))
        return self

    def contrast(self, lower: float = 0.8, upper: float = 1.2, p: float = 0.5):
        self._aug.add(contrast(lower, upper, p))
        return self

    def cutout(self, size: int = 8, p: float = 0.5):
        self._aug.add(cutout(size, p, self.data_format))
        return self

    def gaussian_noise(self, std: float = 0.05, p: float = 0.5):
        self._aug.add(gaussian_noise(std, p))
        return self

    def horizontal_flip(self, p: float = 0.5):
        self._aug.add(horizontal_flip(p, self.data_format))
        return self

    def vertical_flip(self, p: float = 0.5):
        self._aug.add(vertical_flip(p, self.data_format))
        return self

    def normalization(self, mean: Sequence[float], std: Sequence[float]):
        self._aug.add(normalization(mean, std, self.data_format))
        return self

    def random_crop(self, padding: int = 4, p: float = 1.0):
        self._aug.add(random_crop(padding, p, self.data_format))
        return self

    def rotation(self, max_degrees: float = 15.0, p: float = 0.5):
        self._aug.add(rotation(max_degrees, p, self.data_format))
        return self

    def build(self) -> DeviceAugment:
        return self._aug
