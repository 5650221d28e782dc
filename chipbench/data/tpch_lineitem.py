"""TPC-H ``lineitem``, all 16 columns, generated on the device from a seed.

Follows the TPC-H specification (v3.0.1): the column types of clause
1.4.1 and the domains of clause 4.2.3, with P parts and S suppliers
(200,000 and 10,000 per unit of scale factor):

- ``l_orderkey``: sparse keys, the first 8 of every 32 (clause 4.2.3's
  ``O_ORDERKEY``), one order per 1 to 7 lineitems, rows clustered by key as
  dbgen writes them;
- ``l_partkey``: 1 to P; ``l_suppkey``: one of the part's four suppliers,
  ``(p + i (S/4 + (p - 1)/S)) mod S + 1`` for a part key p and i from 0 to 3;
- ``l_linenumber``: 1 to the order's line count;
- ``l_quantity``: 1 to 50;
- ``l_extendedprice``: quantity times the part's retail price,
  ``(90000 + (p/10) mod 20001 + 100 (p mod 1000)) / 100``;
- ``l_discount``: 0.00 to 0.10, ``l_tax``: 0.00 to 0.08;
- ``l_shipdate``: the order's date plus 1 to 121 days, with the order date
  drawn from STARTDATE to ENDDATE - 151 days; ``l_commitdate``: the order's
  date plus 30 to 90 days; ``l_receiptdate``: the ship date plus 1 to 30;
- ``l_returnflag``: ``R`` or ``A`` when the receipt date is on or before
  CURRENTDATE, else ``N``; ``l_linestatus``: ``O`` when the ship date is
  after CURRENTDATE, else ``F``;
- ``l_shipinstruct`` (CHAR(25)) and ``l_shipmode`` (CHAR(10)): one of the
  clause's 4 instructions and 7 modes;
- ``l_comment`` (VARCHAR(44)): 10 to 43 characters.  dbgen draws the words
  of the clause's text grammar; here each character is a lower-case letter
  or a space.

Identifiers, integers and dates are int32 (dates in days since
1970-01-01); decimals are float32; text is bytes: a ``CHAR(1)`` column is
uint8, a wider ``CHAR(n)`` a ``(rows, n)`` uint8 array padded with spaces,
and ``VARCHAR(44)`` a ``(rows, 44)`` one padded with zero bytes.

The number of lineitems of each order is not drawn from the seed: the
multiset of counts is fixed (1 to 7, as evenly as the order count allows,
topped up to the stated lineitem count) and its order is a permutation
drawn from a constant key.  So every seed has the same orders, the same
group sizes and the same row count, and only the values change.
"""
from __future__ import annotations

import datetime
import functools

import jax
import jax.numpy as jnp
import numpy as np

_EPOCH = datetime.date(1970, 1, 1)


def day(y: int, m: int, d: int) -> int:
    """Days since 1970-01-01."""
    return (datetime.date(y, m, d) - _EPOCH).days


STARTDATE = day(1992, 1, 1)
ENDDATE = day(1998, 12, 31)
CURRENTDATE = day(1995, 6, 17)

RETURNFLAGS = b"ANR"
LINESTATUSES = b"FO"
INSTRUCTIONS = ("DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN")
MODES = ("REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB")

#: every column's stored dtype and bytes per element (its text width)
COLUMNS = {
    "l_orderkey": (jnp.int32, 1),
    "l_partkey": (jnp.int32, 1),
    "l_suppkey": (jnp.int32, 1),
    "l_linenumber": (jnp.int32, 1),
    "l_quantity": (jnp.float32, 1),
    "l_extendedprice": (jnp.float32, 1),
    "l_discount": (jnp.float32, 1),
    "l_tax": (jnp.float32, 1),
    "l_returnflag": (jnp.uint8, 1),
    "l_linestatus": (jnp.uint8, 1),
    "l_shipdate": (jnp.int32, 1),
    "l_commitdate": (jnp.int32, 1),
    "l_receiptdate": (jnp.int32, 1),
    "l_shipinstruct": (jnp.uint8, 25),
    "l_shipmode": (jnp.uint8, 10),
    "l_comment": (jnp.uint8, 44),
}

_STRUCTURE_KEY = 20250504  # fixes the order of the lines-per-order counts


def row_bytes(columns) -> int:
    """Stored bytes of one row of the named columns."""
    return sum(np.dtype(COLUMNS[c][0]).itemsize * COLUMNS[c][1] for c in columns)


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed below 2**64 (``jax.random.key``
    keeps only 32 bits of a larger one)."""
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def base_lineitems(orders: int) -> int:
    """Lineitems when order ``i`` has ``1 + i mod 7`` lines."""
    q, r = divmod(orders, 7)
    return orders + 21 * q + r * (r - 1) // 2


def orderkey(order):
    """The key of the ``order``-th order: the first 8 of every 32."""
    return (order // 8) * 32 + order % 8 + 1


def generate(seed: int, *, orders: int, lineitems: int | None, parts: int,
             suppliers: int, sharding=None) -> dict:
    """Every column of ``lineitem`` from one jitted call: on the default
    device, or laid out by ``sharding`` (rows split over a mesh, say, when
    the row count divides by the mesh's size).  ``lineitems`` None keeps
    the base count of :func:`base_lineitems`; a larger one (at most
    ``ceil(orders / 7)`` more) adds one line to that many one-line
    orders."""
    base = base_lineitems(orders)
    total = base if lineitems is None else lineitems
    if not base <= total <= base + -(-orders // 7):
        raise ValueError(f"{total} lineitems do not fit {orders} orders")
    gen = _generate if sharding is None else jax.jit(
        _generate, static_argnames=_STATIC, out_shardings=sharding)
    out = gen(key_from_seed(seed), orders=orders, lineitems=total,
              extra=total - base, parts=parts, suppliers=suppliers)
    jax.block_until_ready(out)
    return out


def _text(key, strings, width: int, rows: int) -> jax.Array:
    """``CHAR(width)`` bytes of one of ``strings`` per row, drawn from
    ``key`` and padded with spaces; a chain of selects (not a gather), so
    no padded copy of the column is made on the way."""
    pick = jax.random.randint(key, (rows, 1), 0, len(strings))
    out = jnp.zeros((rows, width), jnp.uint8)
    for i, s in enumerate(strings):
        out = jnp.where(pick == i, jnp.asarray(list(s.ljust(width).encode()), jnp.uint8), out)
    return out


_STATIC = ("orders", "lineitems", "extra", "parts", "suppliers")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _generate(key, *, orders, lineitems, extra, parts, suppliers):
    idx = jnp.arange(orders, dtype=jnp.int32)
    counts = 1 + idx % 7
    counts = counts + ((idx % 7 == 0) & (idx // 7 < extra)).astype(jnp.int32)
    counts = jax.random.permutation(jax.random.key(_STRUCTURE_KEY), counts)
    order = jnp.repeat(idx, counts, total_repeat_length=lineitems)
    first = jnp.cumsum(counts) - counts

    k = jax.random.split(key, 8)
    m = jax.random.split(jax.random.fold_in(key, 1), 6)
    n = (lineitems,)
    qty = jax.random.randint(k[0], n, 1, 51, jnp.int32)
    part = jax.random.randint(k[1], n, 1, parts + 1, jnp.int32)
    retail_cents = 90000 + (part // 10) % 20001 + 100 * (part % 1000)
    orderdate = jax.random.randint(k[4], (orders,), STARTDATE, ENDDATE - 151 + 1,
                                   jnp.int32)[order]
    ship = orderdate + jax.random.randint(k[5], n, 1, 122, jnp.int32)
    receipt = ship + jax.random.randint(k[6], n, 1, 31, jnp.int32)
    returned = jax.random.bernoulli(k[7], 0.5, n)
    supp_i = jax.random.randint(m[0], n, 0, 4, jnp.int32)
    length = jax.random.randint(m[4], n + (1,), 10, 44, jnp.int32)
    letter = jax.random.randint(m[5], n + (44,), 0, 27, jnp.uint8)  # 26: a space
    made = {
        "l_orderkey": orderkey(order),
        "l_partkey": part,
        "l_suppkey": (part + supp_i * (suppliers // 4 + (part - 1) // suppliers))
        % suppliers + 1,
        "l_linenumber": jnp.arange(lineitems, dtype=jnp.int32) - first[order] + 1,
        "l_quantity": qty.astype(jnp.float32),
        "l_extendedprice": (qty * retail_cents).astype(jnp.float32) / 100,
        "l_discount": jax.random.randint(k[2], n, 0, 11).astype(jnp.float32) / 100,
        "l_tax": jax.random.randint(k[3], n, 0, 9).astype(jnp.float32) / 100,
        "l_returnflag": jnp.where(
            receipt <= CURRENTDATE, jnp.where(returned, ord("R"), ord("A")), ord("N")
        ),
        "l_linestatus": jnp.where(ship > CURRENTDATE, ord("O"), ord("F")),
        "l_shipdate": ship,
        "l_commitdate": orderdate + jax.random.randint(m[1], n, 30, 91, jnp.int32),
        "l_receiptdate": receipt,
        "l_shipinstruct": _text(m[2], INSTRUCTIONS, 25, lineitems),
        "l_shipmode": _text(m[3], MODES, 10, lineitems),
        "l_comment": jnp.where(jnp.arange(44) < length,
                               jnp.where(letter == 26, ord(" "), ord("a") + letter), 0),
    }
    return {c: made[c].astype(COLUMNS[c][0]) for c in COLUMNS}
