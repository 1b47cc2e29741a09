"""`core/kernels.lex_order_traced` (the order of several sort keys from
two-operand sorts) against one `lax.sort` over all the operands at once, which
is what it replaces where that sort compiles for minutes on the chip."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blaze_tpu.core import kernels as K
from blaze_tpu.ops import sort as S
from blaze_tpu.ops import sort_keys as SK

_VALUES = {
    "i64": lambda rng, n: rng.integers(-3, 3, n).astype(np.int64) * (1 << 40) - 1,
    "i32": lambda rng, n: rng.integers(-3, 3, n).astype(np.int32),
    "i8": lambda rng, n: rng.integers(-100, 100, n).astype(np.int8),
    "u8": lambda rng, n: rng.integers(0, 2, n).astype(np.uint8),
    "f32": lambda rng, n: rng.choice(
        [-1.5, -0.0, 0.0, 2.0, 1e30, -1e30, np.inf, -np.inf], n).astype(np.float32),
}


def _operands(rng, n, kinds):
    """Operands as `kernels._key_ops_traced` emits them: ranks 0/1 before,
    2 by value, 3/4 after, 6 padding; value 0 wherever the rank is not 2."""
    exists = np.arange(n) < rng.integers(1, n + 1)
    ops = []
    for kind in kinds:
        rank = rng.choice([0, 1, 2, 2, 2, 2, 3, 4], n)
        rank = np.where(exists, rank, 6).astype(np.uint8)
        val = _VALUES[kind](rng, n)
        ops += [jnp.asarray(rank), jnp.asarray(np.where(rank == 2, val, 0).astype(val.dtype))]
    return tuple(ops)


def _one_sort(ops):
    n = ops[0].shape[0]
    return np.asarray(jax.lax.sort(ops + (jnp.arange(n, dtype=jnp.int32),),
                                   num_keys=len(ops))[-1])


# 8 rows pack every key into one word; 1,024 rows and seven keys need a second
# round of ranks (11 + 4 bits a key); 65,536 rows, three keys to a word
@pytest.mark.parametrize("n", [8, 1024, 65536])
@pytest.mark.parametrize("kinds", [
    ("i64", "i64"), ("i64", "i64", "i64"), ("f32", "i32"), ("u8", "i8", "f32"),
    ("i64", "u8", "i32", "f32", "i8", "i64", "i32"), ("u8",) * 8,
], ids="-".join)
def test_sort_order_is_one_sorts_order(n, kinds):
    rng = np.random.default_rng(n + len(kinds))
    ops = _operands(rng, n, kinds)
    assert np.array_equal(np.asarray(S.sort_order(ops)), _one_sort(ops))


def test_key_is_equal_exactly_where_all_columns_are():
    rng = np.random.default_rng(7)
    n = 512
    a = rng.integers(0, 4, n).astype(np.uint64)
    b = rng.integers(0, 4, n).astype(np.uint64) << np.uint64(60)
    cls = rng.choice([-1, 0, 0, 0, 2], n).astype(np.int8)
    order, key = jax.jit(K.lex_order_traced)(
        [(jnp.asarray(a), jnp.asarray(cls)), (jnp.asarray(b), None)])
    order, key = np.asarray(order), np.asarray(key)
    rows = [(int(c), int(x) if c == 0 else 0, int(y)) for c, x, y in zip(cls, a, b)]
    assert [rows[i] for i in order] == sorted(rows)
    assert sorted(order) == list(range(n))
    same_key = key[1:] == key[:-1]
    same_row = np.array([rows[i] == rows[j] for i, j in zip(order[1:], order[:-1])])
    assert np.array_equal(same_key, same_row)
    # ties keep the rows' order
    assert all(i > j for i, j, s in zip(order[1:], order[:-1], same_key) if s)


@pytest.mark.parametrize("dtype,packs", [
    (jnp.int64, True), (jnp.int8, True), (jnp.uint8, True), (jnp.bool_, True),
    (jnp.float32, True), (jnp.float64, False), (jnp.float16, False)])
def test_which_operands_pack(dtype, packs):
    assert SK.packs_to_word(dtype) is packs


def test_one_key_and_f64_keys_keep_the_single_sort(monkeypatch):
    called = []
    monkeypatch.setattr(S, "sort_order", lambda ops: called.append(len(ops)))
    rank = jnp.full(16, 2, jnp.uint8)
    i64 = jnp.arange(16, dtype=jnp.int64)[::-1]
    assert list(np.asarray(S._device_sort_indices([rank, i64], 16))) == list(range(15, -1, -1))
    f64 = jnp.arange(16, dtype=jnp.float64)
    assert list(np.asarray(S._device_sort_indices([rank, i64 * 0, rank, f64], 16))) == list(range(16))
    assert called == []
    S._device_sort_indices([rank, i64, rank, i64], 16)
    assert called == [4]
