"""Binning at width (io/bin_mapper.py, io/dataset.py): the vectorised bin
search against a value-by-value port of the reference's (the loop this
repo ran until 2 000 columns made it minutes long), the concurrent column
and row-block paths against the serial ones, and EFB's search skipped
where no two columns are sparse enough to share one.
"""
import math

import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.io import bin_mapper as bm
from lightgbm_tpu.io import dataset as dsm
from lightgbm_tpu.io.dataset import BinnedDataset


# ------------------------------------------------------------------ #
# the oracle: GreedyFindBin and the distinct-value chain, one value at
# a time (src/io/bin.cpp:73-149, 238-268)
# ------------------------------------------------------------------ #
def _oracle_distinct(sorted_values, zero_cnt):
    uniq, ucnt = (np.unique(sorted_values, return_counts=True)
                  if len(sorted_values) else (np.empty(0), np.empty(0, int)))
    distinct, counts = [], []
    if len(sorted_values) == 0 or (uniq[0] > 0.0 and zero_cnt > 0):
        distinct.append(0.0)
        counts.append(zero_cnt)
    for cur, c in zip(map(float, uniq), map(int, ucnt)):
        if distinct and distinct[-1] != 0.0 \
                and cur <= math.nextafter(distinct[-1], math.inf) \
                and not (distinct[-1] < 0.0 < cur):
            distinct[-1] = cur
            counts[-1] += c
        else:
            if distinct and distinct[-1] < 0.0 and cur > 0.0:
                distinct.append(0.0)
                counts.append(zero_cnt)
            distinct.append(cur)
            counts.append(c)
    if len(sorted_values) and uniq[-1] < 0.0 and zero_cnt > 0:
        distinct.append(0.0)
        counts.append(zero_cnt)
    return distinct, counts


def _oracle_greedy(values, counts, max_bin, total_cnt, min_data_in_bin):
    n = len(values)
    bounds = []

    def push(val):
        if not bounds or not val <= math.nextafter(bounds[-1], math.inf):
            bounds.append(val)
            return True
        return False
    if n <= max_bin:
        cur = 0
        for i in range(n - 1):
            cur += counts[i]
            if cur >= min_data_in_bin and push(math.nextafter(
                    (values[i] + values[i + 1]) / 2.0, math.inf)):
                cur = 0
        return bounds + [math.inf]
    if min_data_in_bin > 0:
        max_bin = max(1, min(max_bin, int(total_cnt // min_data_in_bin)))
    mean = total_cnt / max_bin
    rest_bins, rest_cnt = max_bin, int(total_cnt)
    big = [c >= mean for c in counts]
    for i in range(n):
        if big[i]:
            rest_bins -= 1
            rest_cnt -= counts[i]
    mean = rest_cnt / rest_bins if rest_bins > 0 else math.inf
    upper, lower = [math.inf] * max_bin, [math.inf] * max_bin
    bin_cnt, cur = 0, 0
    lower[0] = values[0]
    for i in range(n - 1):
        if not big[i]:
            rest_cnt -= counts[i]
        cur += counts[i]
        if big[i] or cur >= mean or (
                big[i + 1] and cur >= max(1.0, mean * np.float32(0.5))):
            upper[bin_cnt] = values[i]
            bin_cnt += 1
            lower[bin_cnt] = values[i + 1]
            if bin_cnt >= max_bin - 1:
                break
            cur = 0
            if not big[i]:
                rest_bins -= 1
                mean = rest_cnt / rest_bins if rest_bins > 0 else math.inf
    for i in range(bin_cnt):
        push(math.nextafter((upper[i] + lower[i + 1]) / 2.0, math.inf))
    return bounds + [math.inf]


def _columns():
    rng = np.random.RandomState(0)
    n = 6000
    yield "gauss32", rng.randn(n).astype(np.float32).astype(np.float64)
    yield "ints", rng.randint(-5, 6, n).astype(np.float64)
    yield "few", rng.choice([0.5, 1.5, 7.0], n)
    yield "positive", np.abs(rng.randn(n))
    yield "negative", -np.abs(rng.randn(n))
    yield "sparse", np.where(rng.rand(n) < 0.9, 0.0, rng.randn(n))
    yield "skew", np.concatenate([np.full(n // 2, 3.0), rng.randn(n // 2)])
    yield "bigs", np.concatenate([
        np.full(n // 3, 3.0), np.full(n // 4, -2.0), np.full(n // 5, 9.5),
        rng.randn(n - n // 3 - n // 4 - n // 5) * 10])
    x = rng.randn(n)
    x[1::2] = np.nextafter(x[::2], np.inf)
    yield "ulps", x
    yield "mixed", np.round(rng.randn(n) * 3) \
        + (rng.rand(n) < 0.01) * rng.randn(n)


COLUMNS = dict(_columns())


@pytest.mark.parametrize("name", sorted(COLUMNS))
@pytest.mark.parametrize("zero_cnt", [0, 17])
def test_distinct_values_equal_the_value_by_value_chain(name, zero_cnt):
    col = COLUMNS[name]
    values = np.sort(col[np.abs(col) > 1e-35])
    want_v, want_c = _oracle_distinct(values, zero_cnt)
    got_v, got_c = bm.BinMapper._distinct_with_zero(values, zero_cnt)
    assert got_v.tolist() == want_v and got_c.tolist() == want_c


@pytest.mark.parametrize("name", sorted(COLUMNS))
@pytest.mark.parametrize("max_bin,min_data_in_bin", [
    (63, 3), (255, 3), (16, 1), (4, 100), (63, 400)])
def test_greedy_bins_equal_the_value_by_value_walk(name, max_bin,
                                                   min_data_in_bin):
    col = COLUMNS[name]
    values, counts = _oracle_distinct(np.sort(col[np.abs(col) > 1e-35]), 0)
    total = int(sum(counts))
    assert bm.greedy_find_bin(values, counts, max_bin, total,
                              min_data_in_bin) \
        == _oracle_greedy(values, counts, max_bin, total, min_data_in_bin)


def test_bin_counts_follow_the_bounds():
    """cnt_in_bin by search equals the walk over the distinct values: it
    decides `is_trivial` and `sparse_rate`."""
    for name, col in COLUMNS.items():
        m = bm.BinMapper()
        m.find_bin(col[np.abs(col) > 1e-35], len(col), 63, 3, 5)
        if m.is_trivial:
            continue
        bins = m.values_to_bins(col)
        assert m.sparse_rate == pytest.approx(
            np.mean(bins == m.default_bin), abs=1e-12), name


# ------------------------------------------------------------------ #
# concurrent columns and row blocks against the serial loops
# ------------------------------------------------------------------ #
def _wide(rows=3000, seed=3):
    rng = np.random.RandomState(seed)
    cols = [c[:rows] for c in COLUMNS.values()]
    cols += [rng.randn(rows) for _ in range(40)]
    cols.append(np.where(rng.rand(rows) < 0.1, np.nan, rng.randn(rows)))
    return np.stack(cols, axis=1).astype(np.float32)


def _states(ds):
    return [m.to_state() for m in ds.bin_mappers]


def test_concurrent_mappers_and_bins_equal_the_serial_ones(monkeypatch):
    X = _wide()
    cfg = Config({"max_bin": 63, "enable_bundle": False, "verbose": -1})
    monkeypatch.setattr(dsm, "_BIN_BLOCK_ROWS", 512)     # six row blocks
    many = BinnedDataset.construct(X, cfg)
    monkeypatch.setattr(dsm.os, "cpu_count", lambda: 1)  # the serial loops
    one = BinnedDataset.construct(X, cfg)
    assert repr(_states(many)) == repr(_states(one))     # NaN bounds too
    np.testing.assert_array_equal(many.bins, one.bins)
    # and column by column, as before the row blocks
    for inner, raw in enumerate(many.real_feature_index):
        np.testing.assert_array_equal(
            many.bins[:, inner],
            many.bin_mappers[inner].values_to_bins(
                X[:, raw].astype(np.float64)))


def test_map_columns_keeps_order_and_raises_what_a_call_raises():
    assert dsm._map_columns(lambda i: i * i, range(50)) \
        == [i * i for i in range(50)]
    assert dsm._map_columns(lambda i: i, []) == []

    def boom(i):
        if i == 7:
            raise ValueError("column 7")
        return i
    with pytest.raises(ValueError, match="column 7"):
        dsm._map_columns(boom, range(20))


# ------------------------------------------------------------------ #
# EFB's search at width
# ------------------------------------------------------------------ #
def test_efb_search_is_skipped_where_no_two_columns_are_sparse(monkeypatch):
    from lightgbm_tpu.io import efb
    monkeypatch.setattr(efb, "find_groups", lambda *a, **k: pytest.fail(
        "the conflict search ran on dense columns"))
    rng = np.random.RandomState(0)
    X = rng.randn(2000, 64).astype(np.float32)
    X[:, 5] = np.where(rng.rand(2000) < 0.95, 0.0, X[:, 5])   # one alone
    ds = BinnedDataset.construct(X, Config({"max_bin": 63, "verbose": -1}))
    assert ds.num_features == 64 and ds.bundle is None


def test_efb_still_bundles_sparse_columns():
    rng = np.random.RandomState(0)
    X = rng.randn(2000, 12).astype(np.float32)
    owner = rng.randint(0, 8, 2000)
    for j in range(8):                    # eight mutually exclusive columns
        X[:, j] = np.where(owner == j, np.abs(X[:, j]) + 1, 0.0)
    ds = BinnedDataset.construct(X, Config({"max_bin": 63, "verbose": -1}))
    assert ds.bins.shape[1] < 12
