"""Model replica: one decode executor behind the serving dispatch engine.

A Replica is the serving analogue of the Engine's DeviceGroup: it owns
one model instance pinned to one device (its own copy of the parameters
lives there, and every array it makes is placed there) and executes
request packets — batched prefill + greedy decode.  ``device=None`` keeps
the default device.  Heterogeneity across replicas (mixed accelerator
generations, degraded hosts) is emulated with ``throttle`` exactly as in
core/device.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.device import DeviceGroup
from repro.models import transformer as T


class Replica:
    """One model replica with its own decode loop."""

    def __init__(self, name: str, cfg, params, throttle: float = 1.0,
                 device=None):
        self.name = name
        self.cfg = cfg
        self.device = device
        self.params = params if device is None \
            else jax.device_put(params, device)
        self.group = DeviceGroup(name, device=device, throttle=throttle)
        self._decode = jax.jit(
            lambda p, t, c, pos: T.decode_step(cfg, p, t, c, pos))

    def generate(self, prompts, gen: int, cache_len: int = None):
        """prompts: (B, P) -> generated tokens (B, gen), on the replica's
        device.

        ``cache_len`` pins the KV-cache length independently of ``gen`` so
        degraded (shorter) generations reuse the same compiled executables.
        """
        cfg = self.cfg
        B, P = prompts.shape
        with jax.default_device(self.device):
            cache, _ = T.init_cache(cfg, B, cache_len or P + gen)
            lg, cache = T.prefill(cfg, self.params, jnp.asarray(prompts),
                                  cache)
            tok = jnp.argmax(lg[:, -1], -1)[:, None]
            out = []
            for i in range(gen):
                out.append(tok)
                lg, cache = self._decode(self.params, tok, cache,
                                         jnp.int32(P + i))
                tok = jnp.argmax(lg[:, -1], -1)[:, None]
            return jnp.concatenate(out, axis=1)

    def serve(self, prompts, gen: int,
              cache_len: int = None) -> np.ndarray:
        """``generate``, brought back to the host."""
        return np.asarray(self.generate(prompts, gen, cache_len))
