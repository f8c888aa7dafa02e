"""Plain reference of the Gaussian blur: the image made from the seed,
edge-padded, and blurred by a separable filter in straightforward
``jax.numpy`` on the default device.  It shares no code with the
program: the filter and the image are made here again from the
configuration and the seed.

``compare`` gives one number, ``err``: the largest absolute gap between
a served pixel and the reference's, over the root mean square of the
reference's pixels in the sample.  A float32 blur lands within rounding
of the reference; one computed in bfloat16 (the control) does not."""
from __future__ import annotations

from functools import partial
from typing import Dict, Iterable, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def weights(ksize: int) -> np.ndarray:
    """The normalised Gaussian taps, sigma from the kernel size."""
    sigma = 0.3 * ((ksize - 1) * 0.5 - 1) + 0.8
    x = np.arange(ksize) - (ksize - 1) / 2
    w = np.exp(-(x * x) / (2 * sigma * sigma))
    return (w / w.sum()).astype(np.float32)


def image(h: int, w: int, seed: int) -> np.ndarray:
    """The input image: standard normal float32 pixels from ``seed``."""
    rng = np.random.default_rng(seed)
    return rng.standard_normal((h, w)).astype(np.float32)


@partial(jax.jit, static_argnames=("h", "w"))
def _blur(padded, taps, *, h: int, w: int):
    k = taps.shape[0]
    tmp = taps[0] * padded[0:h, :]
    for i in range(1, k):
        tmp = tmp + taps[i] * padded[i:i + h, :]
    out = taps[0] * tmp[:, 0:w]
    for i in range(1, k):
        out = out + taps[i] * tmp[:, i:i + w]
    return out


class Reference:
    """The whole blurred image, computed once, in ``dtype``."""

    def __init__(self, config: Dict, seed: int, dtype: str = "float32"):
        h, w = config["image"]
        k = int(config["ksize"])
        dt = jnp.dtype(dtype)
        padded = np.pad(image(h, w, seed), k // 2, mode="edge")
        out = _blur(jnp.asarray(padded, dt), jnp.asarray(weights(k), dt),
                    h=h, w=w)
        self._out = np.asarray(out.astype(jnp.float32))

    def rows(self, rows: np.ndarray) -> np.ndarray:
        """Full-width output rows ``rows`` (absolute image rows)."""
        return self._out[np.asarray(rows)]


def compare(pairs: Iterable[Tuple[np.ndarray, np.ndarray]]) -> Dict:
    """``err``: max |got - want| over rms(want), over every (got, want)
    pair of samples (one pair per submit, streamed)."""
    worst, sq, n = 0.0, 0.0, 0
    for got, want in pairs:
        g = got.astype(np.float64)
        r = want.astype(np.float64)
        if not np.isfinite(g).all():
            return {"err": float("inf")}
        worst = max(worst, float(np.max(np.abs(g - r), initial=0.0)))
        sq += float(np.sum(r * r))
        n += r.size
    rms = float(np.sqrt(sq / n)) if n and sq > 0 else 1.0
    return {"err": worst / rms}
