"""Columnar core tests (reference analog: be/test/column/)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from starrocks_tpu import types as T
from starrocks_tpu.column import (
    Chunk,
    Field,
    HostTable,
    Schema,
    StringDict,
    chunk_from_arrays,
    pad_capacity,
)


def test_pad_capacity():
    assert pad_capacity(0) == 1024
    assert pad_capacity(1) == 1024
    assert pad_capacity(1024) == 1024
    assert pad_capacity(1025) == 2048


def test_logical_types():
    d = T.DECIMAL(15, 2)
    assert d.dtype == jnp.int64
    assert repr(d) == "DECIMAL(15,2)"
    assert T.common_numeric_type(T.INT, T.BIGINT) == T.BIGINT
    assert T.common_numeric_type(T.INT, T.DOUBLE) == T.DOUBLE
    assert T.common_numeric_type(T.DECIMAL(15, 2), T.DECIMAL(15, 4)).scale == 4
    assert T.common_numeric_type(T.DECIMAL(15, 2), T.INT).is_decimal
    # precision > 18 promotes to the 128-bit limb layout
    assert T.DECIMAL(38, 10).is_decimal128
    with pytest.raises(NotImplementedError):
        T.DECIMAL(39, 10)


def test_string_dict_roundtrip():
    d, codes = StringDict.from_strings(["b", "a", "c", "a"])
    assert list(d.values) == ["a", "b", "c"]
    assert list(codes) == [1, 0, 2, 0]
    assert list(d.decode(codes)) == ["b", "a", "c", "a"]
    assert d.encode_one("c") == 2
    assert d.encode_one("zz") == -1
    lut = d.lut(lambda s: s >= "b")
    assert list(lut) == [False, True, True]


def test_string_dict_merge():
    d1, _ = StringDict.from_strings(["a", "c"])
    d2, _ = StringDict.from_strings(["b", "c"])
    m, r1, r2 = d1.merge(d2)
    assert list(m.values) == ["a", "b", "c"]
    assert list(r1) == [0, 2]
    assert list(r2) == [1, 2]


def _mk_chunk():
    schema = Schema(
        (
            Field("k", T.INT, nullable=False),
            Field("v", T.DOUBLE, nullable=True),
        )
    )
    return chunk_from_arrays(
        schema,
        {"k": np.arange(10, dtype=np.int32), "v": np.arange(10) * 1.5},
        {"v": np.arange(10) % 2 == 0},
    )


def test_chunk_basics():
    c = _mk_chunk()
    assert c.capacity == 1024
    assert int(c.num_rows()) == 10
    k, kv = c.col("k")
    assert kv is None
    v, vv = c.col("v")
    assert vv is not None
    assert bool(vv[0]) and not bool(vv[1])


def test_chunk_is_pytree_and_jittable():
    c = _mk_chunk()
    leaves = jax.tree_util.tree_leaves(c)
    assert len(leaves) == 4  # k, v, v.valid, sel

    @jax.jit
    def double_v(ch: Chunk) -> Chunk:
        v, vv = ch.col("v")
        return ch.with_columns(
            [ch.field("v")], [v * 2.0], [vv]
        )

    out = double_v(c)
    np.testing.assert_allclose(np.asarray(out.col("v")[0])[:10], np.arange(10) * 3.0)
    # second call hits the jit cache (schema aux data is hashable)
    out2 = double_v(c)
    assert double_v._cache_size() == 1


def test_chunk_project_take_sel():
    c = _mk_chunk()
    p = c.project(["v"])
    assert p.schema.names == ("v",)
    t = c.take(jnp.asarray([3, 1, 2]))
    assert list(np.asarray(t.col("k")[0])) == [3, 1, 2]
    s = c.and_sel(jnp.arange(c.capacity) < 5)
    assert int(s.num_rows()) == 5


def test_host_table_roundtrip():
    ht = HostTable.from_pydict(
        {
            "id": np.arange(5, dtype=np.int64),
            "name": ["x", "y", "x", "z", None],
            "amt": [1.5, None, 2.5, 3.0, 4.0],
        }
    )
    assert ht.schema.field("name").type.is_string
    c = ht.to_chunk()
    back = HostTable.from_chunk(c)
    rows = back.to_pylist()
    assert rows[0] == (0, "x", 1.5)
    assert rows[1][2] is None
    assert rows[4][1] is None
    df = back.to_pandas()
    assert df.shape == (5, 3)


@pytest.mark.parametrize("selected", [True, False], ids=["sel", "no_sel"])
def test_from_chunk_crosses_to_the_host_once_and_owns_its_arrays(
        selected, monkeypatch):
    """The live mask, the columns and their validity masks come back in one
    `jax.device_get` (an array at a time, a one-row answer waited for three
    transfers in turn: `fetch_ms` 2.6 on a v5e, PR 32), dead rows dropped,
    the arrays the table's own whether the chunk had a mask or none."""
    c = chunk_from_arrays(
        Schema((Field("k", T.BIGINT), Field("v", T.DOUBLE, nullable=True))),
        {"k": np.arange(4, dtype=np.int64),
         "v": np.array([1.5, 0.0, 2.5, 3.5])},
        {"v": np.array([True, False, True, True])})
    cap = c.capacity
    if selected:
        c = c.with_sel(jnp.arange(cap) % 2 == 0)
    else:
        c = Chunk(c.schema, c.data, c.valid, None)
    calls = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: calls.append(1) or real(x))
    back = HostTable.from_chunk(c)
    assert len(calls) == 1
    assert back.num_rows == (cap // 2 if selected else cap)
    rows = back.to_pylist()
    assert rows[0] == (0, 1.5)
    assert rows[1] == ((2, 2.5) if selected else (1, None))
    for a in (*back.arrays.values(), *back.valids.values()):
        assert a.flags.writeable and a.flags.owndata


def test_host_table_decimal():
    ht = HostTable.from_pydict(
        {"price": [1.23, 4.56]}, types={"price": T.DECIMAL(15, 2)}
    )
    assert list(ht.arrays["price"]) == [123, 456]
    assert ht.to_pylist()[0][0] == 1.23


def test_from_arrow():
    pa = pytest.importorskip("pyarrow")
    t = pa.table(
        {
            "a": pa.array([1, 2, None], type=pa.int64()),
            "s": pa.array(["p", None, "q"]),
            "d": pa.array([18000, 18001, 18002], type=pa.date32()),
        }
    )
    ht = HostTable.from_arrow(t)
    rows = ht.to_pylist()
    assert rows[0][0] == 1 and rows[2][0] is None
    assert rows[0][1] == "p" and rows[1][1] is None
    assert rows[0][2] == "2019-04-14"


def test_empty_dict_decode():
    d = StringDict.from_values([])
    assert list(d.decode(np.array([0, 3, -1]))) == ["", "", ""]


def test_empty_table_operator_sweep():
    # every operator shape over an empty table must return cleanly
    from starrocks_tpu.runtime.session import Session

    s = Session()
    s.sql("create table e1 (k int, g varchar, v double)")
    s.sql("create table f1 (k int, g varchar, v double)")
    s.sql("insert into f1 values (1, 'a', 1.0)")
    assert s.sql("select g, sum(v) s from e1 group by g").rows() == []
    assert s.sql("select count(*) c, sum(v) s from e1").rows() == [(0, None)]
    assert s.sql("select f1.k from f1 left join e1 on f1.k = e1.k").rows() == [(1,)]
    assert s.sql("select g, sum(v) s from e1 group by rollup(g)").rows() == [(None, None)]
    assert s.sql("select k, rank() over (order by v) r from e1").rows() == []
    assert s.sql("select count(distinct g) c from e1").rows() == [(0,)]
    assert s.sql("select k from e1 union all select k from f1").rows() == [(1,)]
