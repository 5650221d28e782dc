"""Each configuration at a tiny size on the CPU: the generator's columns and
domains, the query against its float64 reference, the control against the
limits, what each comparison catches, and the bytes count against a hand
count."""
import jax
import numpy as np
import pytest

from chipbench import harness, keys
from chipbench.data import tpch_lineitem as L
from chipbench.tests.tiny import tiny
from repro.engine.columns import combine_keys
from repro.engine.plans import Scan

CONFIGS = ("tpch_q1_sf1", "tpch_q18agg_sf1")


def _load(name):
    cfg, mod = harness.load_config(harness.load_benchmark(), name)
    cfg.update(tiny())
    return cfg, mod


def _answer_and_reference(name, seed, **over):
    cfg, mod = _load(name)
    cfg.update(over)
    data = mod.make_data(cfg, seed)
    got = mod.answer(cfg, mod.PLAN.collect(Scan(mod.prepare(cfg, data))))
    ref = mod.reference(cfg, jax.device_get({c: data[c] for c in mod.READS}))
    return cfg, mod, got, ref


def test_sf1_row_count_is_tpch():
    cfg, _ = harness.load_config(harness.load_benchmark(), "tpch_q1_sf1")
    assert cfg["lineitems"] == 6_001_215 and cfg["orders"] == 1_500_000
    assert cfg["parts"] == 200_000 and cfg["suppliers"] == 10_000
    extra = cfg["lineitems"] - L.base_lineitems(cfg["orders"])
    assert 0 <= extra <= -(-cfg["orders"] // 7)


def test_generator_domains_and_fixed_structure():
    kw = dict(orders=700, lineitems=L.base_lineitems(700) + 50, parts=2000,
              suppliers=100)
    a = jax.device_get(L.generate(1, **kw))
    b = jax.device_get(L.generate(2**40 + 1, **kw))
    c = jax.device_get(L.generate(1 + 2**32, **kw))
    rows = kw["lineitems"]
    assert set(a) == set(L.COLUMNS) and len(a) == 16
    for name, (dtype, width) in L.COLUMNS.items():
        assert a[name].dtype == dtype
        assert a[name].shape == ((rows,) if width == 1 else (rows, width))
    # the same orders and group sizes for every seed, clustered by key
    np.testing.assert_array_equal(a["l_orderkey"], b["l_orderkey"])
    assert np.all(np.diff(np.unique(a["l_orderkey"], return_index=True)[1]) > 0)
    assert set(np.unique(a["l_orderkey"]) % 32) <= set(range(1, 9))
    counts = np.unique(a["l_orderkey"], return_counts=True)[1]
    assert counts.min() >= 1 and counts.max() <= 7
    # line numbers run 1, 2, ... within each order
    first = np.r_[True, a["l_orderkey"][1:] != a["l_orderkey"][:-1]]
    assert np.all(a["l_linenumber"][first] == 1)
    assert np.all(np.diff(a["l_linenumber"])[~first[1:]] == 1)
    assert 1 <= a["l_partkey"].min() and a["l_partkey"].max() <= 2000
    assert 1 <= a["l_suppkey"].min() and a["l_suppkey"].max() <= 100
    assert 1 <= a["l_quantity"].min() and a["l_quantity"].max() <= 50
    assert 0 <= a["l_discount"].min() and a["l_discount"].max() <= np.float32(0.10)
    assert 0 <= a["l_tax"].min() and a["l_tax"].max() <= np.float32(0.08)
    assert set(np.unique(a["l_returnflag"])) <= {ord("A"), ord("N"), ord("R")}
    assert set(np.unique(a["l_linestatus"])) <= {ord("F"), ord("O")}
    received = a["l_receiptdate"] - a["l_shipdate"]
    assert received.min() >= 1 and received.max() <= 30
    committed = a["l_commitdate"] - a["l_shipdate"]  # (30..90) - (1..121)
    assert committed.min() >= 30 - 121 and committed.max() <= 90 - 1
    # N exactly when not yet received, O exactly when not yet shipped
    shipped = a["l_shipdate"] <= L.CURRENTDATE
    assert np.all((a["l_linestatus"] == ord("F")) == shipped)
    assert np.all((a["l_returnflag"] == ord("N")) == (a["l_receiptdate"] > L.CURRENTDATE))
    # text: the clause's words, space-padded; comments of 10 to 43 characters
    assert {bytes(r).decode().rstrip() for r in a["l_shipinstruct"]} <= set(L.INSTRUCTIONS)
    assert {bytes(r).decode().rstrip() for r in a["l_shipmode"]} <= set(L.MODES)
    length = (a["l_comment"] != 0).sum(axis=1)
    assert length.min() >= 10 and length.max() <= 43
    assert set(np.unique(a["l_comment"])) <= set(b"abcdefghijklmnopqrstuvwxyz \0")
    # values follow the seed, all 64 bits of it
    assert not np.array_equal(a["l_quantity"], b["l_quantity"])
    assert not np.array_equal(a["l_quantity"], c["l_quantity"])
    assert not np.array_equal(a["l_comment"], c["l_comment"])


def test_key_dictionary_decodes_the_engines_hash():
    flag = np.array([65, 78, 82], np.uint8)
    status = np.array([70, 79], np.uint8)
    d = keys.KeyDictionary(flag, status)
    h = combine_keys(jax.numpy.asarray([82, 65]), jax.numpy.asarray([79, 70]))
    (f, s), found = keys.decode(d.hashes, d.values, h)
    assert list(f) == [82, 65] and list(s) == [79, 70] and all(found)
    _, found = keys.decode(d.hashes, d.values, combine_keys(jax.numpy.asarray([1]),
                                                            jax.numpy.asarray([2])))
    assert not any(found)


@pytest.mark.parametrize("hash_changed", [False, True])
def test_decode_one_computes_or_looks_up(hash_changed, monkeypatch):
    """One integer key: the value computed from the engine's hash, or, where
    that check fails, the dictionary's; padding past the live keys is not
    looked at."""
    domain = L.orderkey(np.arange(5000, dtype=np.int32))
    d = keys.KeyDictionary(domain)
    if hash_changed:
        monkeypatch.setattr(keys, "_unmix", lambda h: h)
    picked = domain[[17, 4999, 0, 2500]]
    h = jax.numpy.concatenate([combine_keys(jax.numpy.asarray(picked)),
                               jax.numpy.full((2,), 0xFFFFFFFF, jax.numpy.uint32)])
    live = jax.numpy.arange(6) < 4
    (v,), found = keys.decode_one(d.hashes, d.values, h, live)
    assert list(v[:4]) == list(picked) and all(found[:4])


@pytest.mark.parametrize("name", CONFIGS)
def test_query_matches_reference(name):
    cfg, mod, got, ref = _answer_and_reference(name, 11)
    numbers = mod.compare(got, ref)
    assert numbers.keys() == cfg["limits"].keys()
    for k, v in numbers.items():
        assert v <= cfg["limits"][k], (k, v)


def test_q1_answer_is_ordered_and_its_order_compared():
    _, mod, got, ref = _answer_and_reference("tpch_q1_sf1", 13)
    assert [(chr(f), chr(s)) for f, s in zip(got["l_returnflag"], got["l_linestatus"])] \
        == [("A", "F"), ("N", "F"), ("N", "O"), ("R", "F")]
    swapped = {k: v[[1, 0, 2, 3]] for k, v in got.items()}
    assert mod.compare(swapped, ref)["rows_wrong"] == 2
    assert mod.compare({k: v[:3] for k, v in got.items()}, ref)["rows_wrong"] == 1


def test_q18_having_rows_compared():
    # at the tiny size hardly an order passes 300; 200 leaves some rows
    cfg, mod, got, ref = _answer_and_reference("tpch_q18agg_sf1", 14, quantity=200)
    assert got["having"].any() and not got["having"].all()
    np.testing.assert_array_equal(got["having"], got["sum(l_quantity)"] > cfg["quantity"])
    flipped = dict(got, having=got["having"].copy())
    flipped["having"][np.argmax(~got["having"])] = True
    numbers = mod.compare(flipped, ref)
    assert numbers["having_wrong"] == 1 and numbers["sum_err"] == 0


@pytest.mark.parametrize("name", CONFIGS)
def test_control_fails_a_limit(name):
    cfg, mod = _load(name)
    data = mod.make_data(cfg, 12)
    h = jax.device_get({c: data[c] for c in mod.READS})
    numbers = mod.compare(mod.control(cfg, h), mod.reference(cfg, h))
    assert any(numbers[k] > cfg["limits"][k] for k in numbers), numbers


def test_bytes_hand_count():
    cfg, q1 = _load("tpch_q1_sf1")
    rows = cfg["lineitems"]
    # flags 1 byte each; quantity, price, discount, tax, ship date 4 bytes;
    # 4 answer rows of two 1-byte keys and 8 aggregates
    assert q1.bytes_per_query(cfg) == rows * (1 + 1 + 4 * 5) + 4 * (2 + 8 * 4)
    cfg, q18 = _load("tpch_q18agg_sf1")
    # key and quantity 4 bytes each; one result row of key and sum per order
    assert q18.bytes_per_query(cfg) == cfg["lineitems"] * 8 + cfg["orders"] * 8
    # a whole row: 4 ids and integers, 4 decimals, 2 flags, 3 dates, 79 text
    assert L.row_bytes(L.COLUMNS) == 4 * 4 + 4 * 4 + 2 + 3 * 4 + 25 + 10 + 44
