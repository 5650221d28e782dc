"""Key values from the engine's result table.

The engine reports each group by one 32-bit hash of its key columns
(``repro.engine.columns.combine_keys``), not by their values.  A query's
answer carries the values, so the timed path decodes the hashes through a
dictionary of the key columns' domain, built once in set-up with the
engine's own hash: the sorted hashes and the values each came from.
This is part of the system under test; the references never use it.

A dictionary lookup is a binary search, about 21 gathers over a 1.5M-key
domain, which takes some 0.3 s a query on a v5e.  For one integer column
:func:`decode_one` computes the value from the hash instead, and looks it
up only where that fails.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.engine.columns import combine_keys


class KeyDictionary:
    """Every combination of the given key domains (1-D arrays, one per key
    column, in the plan's key order), on the device, sorted by hash."""

    def __init__(self, *domains):
        grids = jnp.meshgrid(*(jnp.asarray(d) for d in domains), indexing="ij")
        values = [g.reshape(-1) for g in grids]
        h = combine_keys(*values)
        order = jnp.argsort(h)
        self.hashes = h[order]
        if bool(jnp.any(self.hashes[1:] == self.hashes[:-1])):
            raise ValueError("the engine's key hash is not one-to-one on the domain")
        self.values = tuple(v[order] for v in values)


def decode(hashes, values, keys):
    """Each key's values (jittable), and whether the dictionary holds it; a
    key it does not hold gets the values of a neighbour, so callers mask it."""
    pos = jnp.minimum(jnp.searchsorted(hashes, keys), hashes.shape[0] - 1)
    return tuple(v[pos] for v in values), hashes[pos] == keys


_GOLDEN = 0x9E3779B9
_INV_M1 = pow(0x85EBCA6B, -1, 1 << 32)
_INV_M2 = pow(0xC2B2AE35, -1, 1 << 32)


def _unmix(h):
    """The inverse of the engine's hash of one column, ``fmix32(v) + 0x9E3779B9``
    (Murmur3's finalizer is a bijection on 32 bits)."""
    x = h - jnp.uint32(_GOLDEN)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(_INV_M2)
    x = x ^ (x >> 13) ^ (x >> 26)
    x = x * jnp.uint32(_INV_M1)
    return x ^ (x >> 16)


def decode_one(hashes, values, keys, live):
    """:func:`decode` for one integer key column (jittable).  Each value is
    computed from its hash and checked by hashing it again with the
    engine's ``combine_keys``; only if a ``live`` key fails the check (the
    engine's hash is no longer the one :func:`_unmix` inverts) are the keys
    looked up in the dictionary."""
    guess = _unmix(keys).astype(values[0].dtype)
    ok = combine_keys(guess) == keys
    return jax.lax.cond(jnp.all(ok | ~live), lambda: ((guess,), ok),
                        lambda: decode(hashes, values, keys))
