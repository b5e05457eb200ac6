"""Bitmap fan-out parity: Pallas kernel vs XLA scan vs numpy oracle.

On the CPU test mesh the Pallas kernel runs in interpret mode; the
v5e compile is pinned by tests/test_chip_compile.py
(``test_or_bitmaps_dma_compiles``).
"""

import numpy as np
import pytest

from emqx_tpu.ops.bitmap import (BitmapTable, build_bitmaps, or_bitmaps_auto,
                                 or_bitmaps_xla, rows_for_matches, words_for)


def oracle_or(bitmaps: np.ndarray, rows: np.ndarray) -> np.ndarray:
    out = np.zeros((rows.shape[0], bitmaps.shape[1]), dtype=np.uint32)
    for b in range(rows.shape[0]):
        for r in rows[b]:
            if r >= 0:
                out[b] |= bitmaps[r]
    return out


def test_build_bitmaps_bits():
    t = build_bitmaps({3: [0, 31, 32, 95], 7: [1]}, num_filters=8,
                      n_subs=100)
    assert t.n_rows == 2
    r3 = t.big_row[3]
    assert r3 >= 0 and t.big_row[7] >= 0 and t.big_row[0] == -1
    row = t.bitmaps[r3]
    assert row[0] == (1 | (1 << 31))
    assert row[1] == 1
    assert row[2] == (1 << 31)
    # total population = 4 subscribers
    assert sum(bin(int(w)).count("1") for w in row) == 4


def test_words_padding():
    assert words_for(1, tile=1024) == 1024
    assert words_for(1024 * 32, tile=1024) == 1024
    assert words_for(1024 * 32 + 1, tile=1024) == 2048


def test_rows_for_matches_pack_and_overflow():
    import jax.numpy as jnp
    big_row = np.full((16,), -1, np.int32)
    big_row[2] = 0
    big_row[5] = 1
    big_row[9] = 2
    t = BitmapTable(bitmaps=np.zeros((4, 1024), np.uint32),
                    big_row=big_row, n_rows=3, n_subs=10)
    ids = jnp.array([[1, 2, 5, -1], [9, -1, -1, -1], [2, 5, 9, 3]])
    rows, ovf = rows_for_matches(t, ids, mb=2)
    rows = np.asarray(rows)
    assert rows[0].tolist() == [0, 1]          # small id 1 dropped
    assert rows[1].tolist() == [2, -1]
    assert not ovf[0] and not ovf[1]
    assert bool(ovf[2])                        # 3 big rows > mb=2
    assert rows[2].tolist() == [0, 1]          # first mb kept


@pytest.mark.parametrize("tile", [1024, 2048])
def test_or_parity_random(tile):
    rng = np.random.default_rng(0)
    n_subs = tile * 32 * 3 // 2  # 1.5 tiles worth of bits
    n_big = 9
    rows_dict = {
        fid: rng.choice(n_subs, size=rng.integers(1, 500), replace=False)
        for fid in rng.choice(64, size=n_big, replace=False)
    }
    t = build_bitmaps(rows_dict, num_filters=64, n_subs=n_subs, tile=tile)
    B, mb = 5, 4
    rows = np.full((B, mb), -1, np.int32)
    for b in range(B):
        k = rng.integers(0, mb + 1)
        rows[b, :k] = rng.choice(t.n_rows, size=k, replace=False)
    want = oracle_or(t.bitmaps, rows)
    got_xla = np.asarray(or_bitmaps_xla(t.bitmaps, rows))
    got_pl = np.asarray(or_bitmaps_auto(t.bitmaps, rows))
    np.testing.assert_array_equal(got_xla, want)
    np.testing.assert_array_equal(got_pl, want)


def test_or_empty_rows():
    t = build_bitmaps({0: [1]}, num_filters=4, n_subs=64, tile=1024)
    rows = np.full((3, 4), -1, np.int32)
    out = np.asarray(or_bitmaps_auto(t.bitmaps, rows))
    assert out.sum() == 0


def test_rows_for_matches_out_of_capacity_fid_drops():
    """Clamping an out-of-capacity fid would OR in the LAST filter's
    bitmap — an entire unrelated subscriber set."""
    import jax.numpy as jnp

    from emqx_tpu.ops.bitmap import build_bitmaps, rows_for_matches

    bm = build_bitmaps({3: [1, 2, 3]}, 4, 64)
    f_cap = bm.big_row.shape[0]
    ids = jnp.array([[f_cap + 1, 3, -1, -1]], dtype=jnp.int32)
    rows, ovf = rows_for_matches(bm, ids, mb=4)
    got = [int(r) for r in np.asarray(rows)[0] if r >= 0]
    assert got == [0]               # only filter 3's row
    assert not bool(np.asarray(ovf)[0])
