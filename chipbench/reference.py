"""The comparison that decides ``correct``, and the pieces the
configurations' plain references share.

Everything here is NumPy (float64) except :func:`bf16_group_sums`, the
control: the same GROUP BY computed in bfloat16 on the default device.
Nothing is imported from the program under test.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def group_index(*cols: np.ndarray) -> tuple[list, np.ndarray]:
    """The distinct combinations of the key columns, sorted column by
    column (one array per column), and each row's index into them."""
    code, radix = np.zeros(len(cols[0]), np.int64), []
    for c in cols:  # one mixed-radix code, the first column most significant
        c = np.asarray(c, np.int64)
        lo, span = int(c.min()), int(c.max() - c.min()) + 1
        code = code * span + (c - lo)
        radix.append((lo, span))
    uniq, inv = np.unique(code, return_inverse=True)
    values = []
    for lo, span in reversed(radix):
        uniq, digit = np.divmod(uniq, span)
        values.append(digit + lo)
    return values[::-1], inv.reshape(-1)


def group_sum(inv: np.ndarray, groups: int, values: np.ndarray) -> np.ndarray:
    """float64 per-group sums."""
    return np.bincount(inv, weights=values.astype(np.float64), minlength=groups)


def bf16_group_sums(inv: np.ndarray, groups: int, values: dict,
                    block: int = 4096) -> dict:
    """The control: per-group sums computed in bfloat16, block by block as
    the engine consumes morsels, into bfloat16 accumulators.  ``values``
    maps a name to a row vector; returns float64 arrays by name."""
    rows = inv.shape[0]
    pad = -rows % block
    gid = np.concatenate([inv, np.full(pad, groups, inv.dtype)]).astype(np.int32)
    out = {}
    for name, v in values.items():
        vb = np.concatenate([np.asarray(v, np.float32), np.zeros(pad, np.float32)])
        out[name] = np.asarray(
            _bf16_sums(jnp.asarray(gid.reshape(-1, block)),
                       jnp.asarray(vb.reshape(-1, block)), groups=groups),
            np.float64,
        )
    return out


@functools.partial(jax.jit, static_argnames=("groups",))
def _bf16_sums(gid, vals, *, groups):
    def body(acc, xs):
        g, v = xs
        part = jax.ops.segment_sum(v.astype(jnp.bfloat16), g,
                                   num_segments=groups + 1)
        return acc + part[:groups], None

    acc, _ = jax.lax.scan(body, jnp.zeros((groups,), jnp.bfloat16), (gid, vals))
    return acc


def rows_in_order(got: dict, ref: dict, keys) -> tuple[int, np.ndarray]:
    """An ordered answer against the reference's rows, position by
    position: the rows wrong (a position whose key values differ, or a row
    one side lacks), and the positions where both agree."""
    n, m = len(got[keys[0]]), len(ref[keys[0]])
    k = min(n, m)
    same = np.ones(k, bool)
    for c in keys:
        same &= np.asarray(got[c])[:k] == ref[c][:k]
    return int(k - same.sum() + abs(n - m)), np.nonzero(same)[0]


def align(got_keys: np.ndarray, ref_keys: np.ndarray) -> tuple[int, np.ndarray, np.ndarray]:
    """Match an unordered answer's keys to the reference's (sorted, distinct
    keys).  Returns the number of groups wrong (missing, extra or repeated
    keys) and, for the keys both hold, the positions in each."""
    got_keys = np.asarray(got_keys)
    n = got_keys.size
    common, gi, ri = np.intersect1d(got_keys, ref_keys, return_indices=True)
    wrong = (n - common.size) + (ref_keys.size - common.size)
    wrong += n - np.unique(got_keys).size  # a key reported twice counts again
    return int(wrong), gi, ri


def max_abs_err(got: dict, ref: dict, names, gi, ri) -> float:
    err = 0.0
    for name in names:
        g = np.asarray(got[name], np.float64)[gi]
        if g.size:
            err = max(err, float(np.max(np.abs(g - ref[name][ri]))))
    return err


def max_rel_err(got: dict, ref: dict, names, gi, ri) -> float:
    err = 0.0
    for name in names:
        g = np.asarray(got[name], np.float64)[gi]
        r = ref[name][ri]
        if g.size:
            rel = np.abs(g - r) / np.maximum(np.abs(r), 1e-30)
            err = max(err, float(np.max(rel)))
    return err
