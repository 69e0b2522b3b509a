"""Keys from the run's ``--seed``, which may need more than 32 bits."""

from __future__ import annotations

import jax


def prng_key(seed: int, device):
    """A threefry key on ``device`` for any whole-number seed."""
    seed %= 1 << 64
    with jax.default_device(device):
        key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
        return jax.random.fold_in(key, seed >> 32)
