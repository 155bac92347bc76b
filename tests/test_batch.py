"""Batch/Column data model tests (reference test analog:
presto-common block tests, e.g. TestDictionaryBlock / TestPage)."""

import numpy as np
import pytest

from presto_tpu import Batch, Column, BIGINT, DOUBLE, VARCHAR, BOOLEAN
from presto_tpu.batch import bucket_capacity, unify_dictionaries
from presto_tpu.types import decimal_type, parse_type, common_super_type, DOUBLE as D


def test_bucket_capacity():
    assert bucket_capacity(1) == 16
    assert bucket_capacity(16) == 16
    assert bucket_capacity(17) == 32
    assert bucket_capacity(100_000) == 131072


def test_roundtrip_with_nulls():
    b = Batch.from_pydict({
        "a": ([1, None, 3], BIGINT),
        "b": ([1.5, 2.5, None], DOUBLE),
    })
    assert b.capacity == 16
    assert b.num_valid() == 3
    assert b.to_pydict() == {"a": [1, None, 3], "b": [1.5, 2.5, None]}


def test_varchar_dictionary_sorted():
    col = Column.from_pylist(["pear", "apple", None, "apple", "fig"], VARCHAR)
    assert col.dictionary == ("apple", "fig", "pear")
    assert col.to_pylist()[:5] == ["pear", "apple", None, "apple", "fig"]
    # sorted dictionary => code order is collation order
    codes = np.asarray(col.data)[:5]
    assert codes[1] < codes[2+2]  # apple < fig


def test_decimal_exact():
    t = decimal_type(15, 2)
    col = Column.from_pylist([1.07, 2.03, None], t)
    assert np.asarray(col.data)[:2].tolist() == [107, 203]
    assert col.to_pylist()[:3] == [1.07, 2.03, None]


def test_filter_and_compact():
    b = Batch.from_pydict({"x": ([10, 20, 30, 40], BIGINT)})
    import jax.numpy as jnp
    keep = jnp.asarray(np.array([True, False, True, False] + [True] * 12))
    f = b.filter(keep)
    assert f.num_valid() == 2
    assert f.to_pydict()["x"] == [10, 30]
    c = f.compact()
    assert np.asarray(c.row_valid)[:2].tolist() == [True, True]
    assert c.to_pydict()["x"] == [10, 30]


def test_concat():
    b1 = Batch.from_pydict({"x": ([1, 2], BIGINT)})
    b2 = Batch.from_pydict({"x": ([3, None], BIGINT)})
    out = Batch.concat([b1, b2], capacity=16)
    assert out.to_pydict()["x"] == [1, 2, 3, None]


def _lanes(b):
    """Every lane of a batch, dead ones included: (live, not NULL,
    data)."""
    c = b.columns["x"]
    return list(zip(np.asarray(b.row_valid).tolist(),
                    (np.asarray(c.mask) & np.asarray(b.row_valid)).tolist(),
                    np.asarray(c.data).tolist()))


def _concat_inputs():
    import jax.numpy as jnp
    b1 = Batch.from_pydict({"x": ([1, 2], BIGINT)})          # test_concat's
    b2 = Batch.from_pydict({"x": ([3, None], BIGINT)})
    # dead lanes between live ones, and one that still holds a value
    b3 = Batch.from_pydict({"x": ([7, 8, 9, 10], BIGINT)}).filter(
        jnp.asarray(np.array([True, False, True, False] + [True] * 12)))
    return b1, b2, b3


@pytest.mark.parametrize("capacity", (48, 64))
def test_concat_lanes_keeps_every_lane_where_it_arrived(capacity):
    b1, b2, b3 = _concat_inputs()
    out = Batch.concat_lanes([b1, b3, b2], capacity)
    assert out.capacity == capacity
    assert _lanes(out) == _lanes(b1) + _lanes(b3) + _lanes(b2) \
        + [(False, False, 0)] * (capacity - 48)
    assert out.to_pydict()["x"] == [1, 2, 7, 9, 3, None]


def test_concat_lanes_refuses_inputs_past_the_capacity():
    b1, b2, b3 = _concat_inputs()
    with pytest.raises(ValueError, match="do not fit"):
        Batch.concat_lanes([b1, b2, b3], 32)
    with pytest.raises(ValueError, match="dictionaries"):
        Batch.concat_lanes([
            Batch.from_pydict({"s": (["a"], VARCHAR)}),
            Batch.from_pydict({"s": (["b"], VARCHAR)})], 32)


@pytest.mark.parametrize("capacity", (16, 32, 48, 64))
@pytest.mark.parametrize("which", ((0, 1), (0, 2, 1), (2, 2)))
def test_concat_is_concat_lanes_then_pack(which, capacity):
    """Batch.concat's answer for its other callers (sort, window,
    array_agg, the spilled build): live rows first, in arrival order,
    on `capacity` lanes: what packing concat_lanes' batch gives, lane
    for lane, where the inputs fit, and the shrink where they do not."""
    batches = [_concat_inputs()[i] for i in which]
    live = [v for b in batches for v in b.to_pydict()["x"]]
    out = Batch.concat(batches, capacity)
    assert out.capacity == capacity
    assert out.to_pydict()["x"] == live
    assert np.asarray(out.row_valid).tolist() == (
        np.arange(capacity) < len(live)).tolist()
    if sum(b.capacity for b in batches) <= capacity:
        assert _lanes(out) == _lanes(
            Batch.concat_lanes(batches, capacity).compact())


def test_unify_dictionaries():
    c1 = Column.from_pylist(["b", "a"], VARCHAR)
    c2 = Column.from_pylist(["c", "a"], VARCHAR)
    u1, u2 = unify_dictionaries([c1, c2])
    assert u1.dictionary == u2.dictionary == ("a", "b", "c")
    assert u1.to_pylist()[:2] == ["b", "a"]
    assert u2.to_pylist()[:2] == ["c", "a"]


def test_type_parsing_and_coercion():
    assert parse_type("decimal(15,2)").scale == 2
    assert parse_type("varchar(25)").name == "varchar"
    assert common_super_type(parse_type("integer"), parse_type("bigint")).name == "bigint"
    assert common_super_type(parse_type("bigint"), parse_type("double")) == D
    a = decimal_type(15, 2)
    b = decimal_type(10, 4)
    c = common_super_type(a, b)
    assert (c.precision, c.scale) == (17, 4)
