"""The seed every input of a run comes from.

A run's weights and batches come from ``--seed`` through its
architecture's ``make_inputs`` (``chip_bench/archs/<arch>.py``), in one
jitted call on the default device, from the key ``seed_key`` gives.

A training job here is closed-loop: step ``i`` takes batch ``i % ring``
of a ring of ``ring`` distinct batches.
"""
from __future__ import annotations


def seed_key(seed: int):
    """A PRNG key that depends on every bit of a seed of up to 64 bits
    (``jax.random.key`` keeps only the low 32 without x64)."""
    import jax

    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"--seed must be in [0, 2**64), got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)
