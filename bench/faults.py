"""Faults planted in the program underneath a placement sweep's timed path.

Each turns what the window produces wrong in one way a sweep can go wrong;
the comparison that decides ``correct`` has to catch every one. The tests
plant them at small sizes on the CPU, ``bench/calibrate.py --fault`` at a
cell's own size on the chip.
"""

from __future__ import annotations

import numpy as np


def _answer_altered(real):
    # one total of every stream a quarter of a second off, where it is made
    def run_batched(*args, **kwargs):
        out = np.array(real(*args, **kwargs))
        out[..., 5] += 0.25
        return out
    return run_batched


def _half_the_batch(real):
    # half the seeds left out, the rest repeated in their place
    def run_batched(sim, order, step_sets, preds, succs, t0s, prefetch, seeds, **kw):
        half = max(1, len(seeds) // 2)
        args = (sim, order, step_sets, preds, succs, t0s, prefetch)
        out = real(*args, seeds[:half], **kw)
        return np.concatenate([out, out[: len(seeds) - half]], axis=0)
    return run_batched


def _cold_state_unchanged(real):
    # the cold scan's state never leaves "warm": no request is ever cold
    def cold_mask(t0s, warm_end, cold_end, keep_warm, use_pallas):
        import jax.numpy as jnp

        return jnp.zeros(warm_end.shape, bool)
    return cold_mask


def _cold_flipped_late(real):
    # every cold flag of the second half of each stream flipped
    def cold_mask(t0s, warm_end, cold_end, keep_warm, use_pallas):
        import jax.numpy as jnp

        mask = real(t0s, warm_end, cold_end, keep_warm, use_pallas)
        n = mask.shape[-1]
        return mask ^ (jnp.arange(n) >= n // 2)
    return cold_mask


FAULTS = {
    "answer": ("run_batched", _answer_altered),
    "half_batch": ("run_batched", _half_the_batch),
    "cold_state_unchanged": ("_cold_mask", _cold_state_unchanged),
    "cold_flipped_late": ("_cold_mask", _cold_flipped_late),
}


def plant(name: str):
    """Plant fault ``name`` in ``repro.core.jaxsim``; returns the function
    that takes it out again. A program traced before the fault was planted
    keeps running sound: clear JAX's caches around it."""
    from repro.core import jaxsim

    attr, wrap = FAULTS[name]
    real = getattr(jaxsim, attr)
    setattr(jaxsim, attr, wrap(real))
    return lambda: setattr(jaxsim, attr, real)
