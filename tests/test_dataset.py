import os

import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.io.metadata import Metadata
from lightgbm_tpu.io.parser import detect_format, load_text_file


def _make(rng, n=500, f=5, **params):
    X = rng.randn(n, f)
    cfg = Config(params)
    meta = Metadata(n)
    meta.set_label((rng.rand(n) > 0.5).astype(np.float32))
    return BinnedDataset.construct(X, cfg, metadata=meta), X, cfg


def test_construct_basic(rng):
    ds, X, _ = _make(rng)
    assert ds.num_data == 500
    assert ds.num_features == 5
    assert ds.bins.shape == (500, 5)
    assert ds.bins.dtype == np.uint8
    assert ds.num_total_bin == sum(m.num_bin for m in ds.bin_mappers)


def test_trivial_feature_dropped(rng):
    X = rng.randn(300, 4)
    X[:, 2] = 3.0
    cfg = Config()
    ds = BinnedDataset.construct(X, cfg)
    assert ds.num_features == 3
    assert ds.used_feature_map[2] == -1
    assert ds.real_feature_index == [0, 1, 3]


def test_valid_uses_reference_mappers(rng):
    ds, X, cfg = _make(rng)
    Xv = rng.randn(100, 5)
    vd = ds.create_valid(Xv)
    assert vd.bin_mappers is ds.bin_mappers
    # binning a training row through valid path gives identical bins
    vd2 = ds.create_valid(X[:50])
    np.testing.assert_array_equal(vd2.bins, ds.bins[:50])


def test_binary_round_trip(rng, tmp_path):
    ds, X, _ = _make(rng)
    ds.metadata.set_weights(rng.rand(500))
    path = str(tmp_path / "cache.npz")
    ds.save_binary(path)
    ds2 = BinnedDataset.load_binary(path)
    np.testing.assert_array_equal(ds.bins, ds2.bins)
    np.testing.assert_array_equal(ds.feature_offsets, ds2.feature_offsets)
    np.testing.assert_allclose(ds.metadata.label, ds2.metadata.label)
    np.testing.assert_allclose(ds.metadata.weights, ds2.metadata.weights)
    for m1, m2 in zip(ds.bin_mappers, ds2.bin_mappers):
        np.testing.assert_allclose(m1.bin_upper_bound, m2.bin_upper_bound)


def test_subset(rng):
    ds, X, _ = _make(rng)
    idx = np.arange(0, 500, 7)
    sub = ds.subset(idx)
    np.testing.assert_array_equal(sub.bins, ds.bins[idx])
    np.testing.assert_allclose(sub.metadata.label, ds.metadata.label[idx])


def test_detect_format():
    assert detect_format(["1\t0.5\t0.3"]) == "tsv"
    assert detect_format(["1,0.5,0.3"]) == "csv"
    assert detect_format(["1 2:0.5 7:0.3"]) == "libsvm"


def test_load_reference_example(example_files):
    mat, libsvm_labels, names = load_text_file(example_files["binary.train"])
    assert libsvm_labels is None
    assert mat.shape == (7000, 29)  # label + 28 features
    assert set(np.unique(mat[:, 0])) == {0.0, 1.0}


def test_reference_example_binning(example_files):
    mat, _, _ = load_text_file(example_files["binary.train"])
    y, X = mat[:, 0], mat[:, 1:]
    meta = Metadata(len(y))
    meta.set_label(y)
    ds = BinnedDataset.construct(X, Config({"max_bin": 63}), metadata=meta)
    assert ds.num_features > 0
    assert all(m.num_bin <= 63 for m in ds.bin_mappers)
    # every row binned in range
    for f in range(ds.num_features):
        assert ds.bins[:, f].max() < ds.bin_mappers[f].num_bin


def test_query_metadata():
    meta = Metadata()
    meta.set_label(np.zeros(10))
    meta.set_query([3, 4, 3])
    np.testing.assert_array_equal(meta.query_boundaries, [0, 3, 7, 10])
    assert meta.num_queries == 3
    meta2 = Metadata()
    meta2.set_label(np.zeros(6))
    meta2.set_query_from_ids([5, 5, 7, 7, 7, 9])
    np.testing.assert_array_equal(meta2.query_boundaries, [0, 2, 5, 6])


class TestNativeParserParity:
    """Native fast_parser must agree exactly with the Python fallback:
    same format sniff (colon precedence) and bit-identical floats."""

    def test_libsvm_with_comma_in_line(self, tmp_path):
        # a colon-bearing line that also contains a comma must still sniff
        # as libsvm on BOTH paths (reference parser.cpp:136 precedence)
        from lightgbm_tpu.io import native, parser
        p = tmp_path / "x.txt"
        p.write_text("1 0:1.5 2:2,5\n0 1:3.25\n")
        res = native.parse_file(str(p))
        if res is None:
            pytest.skip("native parser library not built")
        mat, labels, fmt = res
        assert fmt == 2  # libsvm
        assert parser.detect_format(["1 0:1.5 2:2,5"]) == parser.LIBSVM
        np.testing.assert_array_equal(labels, [1.0, 0.0])
        # the Python fallback must parse the same file to the same values
        # (malformed value keeps its leading float, like fast_atof)
        Xp, yp = parser.parse_libsvm(str(p))
        np.testing.assert_array_equal(yp, labels)
        np.testing.assert_array_equal(Xp, mat)

    def test_featureless_first_libsvm_row(self, tmp_path):
        # a bare-label first row is inconclusive: both sniffs must look at
        # the next line and classify the file as libsvm
        from lightgbm_tpu.io import native, parser
        p = tmp_path / "s.txt"
        p.write_text("1\n0 1:3.5 4:2\n")
        assert parser.detect_format(["1", "0 1:3.5 4:2"]) == parser.LIBSVM
        res = native.parse_file(str(p))
        if res is None:
            pytest.skip("native parser library not built")
        mat, labels, fmt = res
        assert fmt == 2
        np.testing.assert_array_equal(labels, [1.0, 0.0])
        assert mat.shape == (2, 5) and mat[1, 1] == 3.5 and mat[1, 4] == 2.0

    def test_float_parity_with_python(self, tmp_path):
        from lightgbm_tpu.io import native
        rows = []
        vals = ["229607991558730021", "1e-7", "3.141592653589793",
                "-0.1", "2.5e300", "123456789012345678901234567890",
                "0.30000000000000004", "7", "-9007199254740993"]
        for i in range(0, len(vals), 3):
            rows.append("\t".join(vals[i:i + 3]))
        p = tmp_path / "f.tsv"
        p.write_text("\n".join(rows) + "\n")
        res = native.parse_file(str(p))
        if res is None:
            pytest.skip("native parser library not built")
        mat, _, fmt = res
        expect = np.array([[float(v) for v in vals[i:i + 3]]
                           for i in range(0, len(vals), 3)])
        np.testing.assert_array_equal(mat, expect)  # bitwise

    def test_exotic_libsvm_indices_parity(self, tmp_path):
        # strtod-parsable indices ('1e2', '2.7') truncate like the native
        # static_cast<int>; float()-only forms ('1_0') are rejected on both
        from lightgbm_tpu.io import native, parser
        p = tmp_path / "e.txt"
        p.write_text("1 1e1:7 2.7:5 1_0:9\n0 0:1\n")
        res = native.parse_file(str(p))
        if res is None:
            pytest.skip("native parser library not built")
        mat, labels, fmt = res
        assert fmt == 2
        Xp, yp = parser.parse_libsvm(str(p), num_features_hint=mat.shape[1])
        np.testing.assert_array_equal(yp, labels)
        np.testing.assert_array_equal(Xp, mat)
        assert mat[0, 10] == 7.0 and mat[0, 2] == 5.0

    def test_overflow_underflow_parity(self, tmp_path):
        from lightgbm_tpu.io import native
        p = tmp_path / "o.tsv"
        p.write_text("1e999\t-1e999\t1e-999\n2\t3\t4\n")
        res = native.parse_file(str(p))
        if res is None:
            pytest.skip("native parser library not built")
        mat, _, fmt = res
        expect = np.array([[float("1e999"), float("-1e999"), float("1e-999")],
                           [2.0, 3.0, 4.0]])
        np.testing.assert_array_equal(mat, expect)

    def test_huge_libsvm_index_dropped_both_paths(self, tmp_path):
        from lightgbm_tpu.io import native, parser
        p = tmp_path / "h.txt"
        p.write_text("1 0:1 inf:3 9999999999:4\n0 1:2\n")
        res = native.parse_file(str(p))
        if res is None:
            pytest.skip("native parser library not built")
        mat, labels, fmt = res
        assert fmt == 2 and mat.shape == (2, 2)
        Xp, yp = parser.parse_libsvm(str(p))
        np.testing.assert_array_equal(Xp, mat)
        np.testing.assert_array_equal(yp, labels)


class TestTwoRound:
    def test_two_round_matches_one_round(self, rng, tmp_path):
        """Streaming (two_round) ingest must produce the same bins,
        metadata and trained model as the in-memory loader."""
        from lightgbm_tpu.config import Config
        from lightgbm_tpu.io import loader as loader_mod
        from lightgbm_tpu.io.dataset import BinnedDataset

        n, F = 3000, 6
        X = rng.randn(n, F)
        y = (X[:, 0] > 0).astype(np.float64)
        w = rng.rand(n) + 0.5
        path = tmp_path / "train.tsv"
        cols = np.column_stack([y, X[:, :3], w, X[:, 3:]])
        np.savetxt(path, cols, delimiter="\t", fmt="%.8g")
        cfg = Config({"label_column": "0", "weight_column": "3",
                      "verbose": -1, "max_bin": 63})

        # one-round oracle
        d = loader_mod.load_data_file(cfg, str(path))
        one = BinnedDataset.construct(d.X, cfg)
        # two-round, small chunks to force many passes
        two = loader_mod.load_two_round(cfg, str(path), chunk_rows=257)

        np.testing.assert_array_equal(one.bins, two.bins)
        np.testing.assert_allclose(np.asarray(two.metadata.label), y)
        np.testing.assert_allclose(np.asarray(two.metadata.weights), w,
                                   rtol=1e-6)   # metadata stores f32
        assert [m.to_state() for m in one.bin_mappers] != []  # sanity

    def test_two_round_cli_train(self, rng, tmp_path):
        """CLI task=train with two_round=true end to end."""
        from lightgbm_tpu.app import Application

        n = 800
        X = rng.randn(n, 5)
        y = (X[:, 0] > 0).astype(np.float64)
        data = tmp_path / "t.csv"
        np.savetxt(data, np.column_stack([y, X]), delimiter=",", fmt="%.7g")
        model = tmp_path / "model.txt"
        conf = tmp_path / "train.conf"
        conf.write_text(
            "task=train\nobjective=binary\ndata=%s\noutput_model=%s\n"
            "two_round=true\nnum_trees=4\nnum_leaves=7\nverbose=-1\n"
            % (data, model))
        Application(["config=%s" % conf]).run()
        assert model.exists() and "tree" in model.read_text()


class TestConstructedMerge:
    """Dataset::addFeaturesFrom / addDataFrom on CONSTRUCTED datasets
    (src/io/dataset.cpp:983): binned feature groups merge in place and
    training on the merged dataset equals training on the jointly-
    constructed one."""

    def test_add_features_from_trains_identically(self, rng):
        import lightgbm_tpu as lgb

        n = 400
        Xa = rng.randn(n, 4)
        Xb = rng.randn(n, 3)
        y = (Xa[:, 0] + Xb[:, 1] > 0).astype(float)
        params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
                  "min_data_in_leaf": 5}

        joint = lgb.train(params, lgb.Dataset(np.column_stack([Xa, Xb]), y),
                          num_boost_round=8)

        da = lgb.Dataset(Xa, y)
        db = lgb.Dataset(Xb)
        da.construct()
        db.construct()
        da.add_features_from(db)
        merged = lgb.train(params, da, num_boost_round=8)

        X = np.column_stack([Xa, Xb])
        np.testing.assert_allclose(joint.predict(X), merged.predict(X),
                                   rtol=1e-6)

    def test_add_features_from_merges_layout(self, rng):
        n = 100
        Xa, Xb = rng.randn(n, 3), rng.randn(n, 2)
        a = BinnedDataset.construct(Xa, Config(max_bin=31))
        b = BinnedDataset.construct(Xb, Config(max_bin=15))
        a.add_features_from(b)
        assert a.num_features == 5
        assert a.num_total_features == 5
        assert a.bins.shape == (n, 5)
        assert len(a.feature_names) == 5
        assert a.real_feature_index == [0, 1, 2, 3, 4]
        # offsets rebuilt over the merged mappers
        assert a.feature_offsets[-1] == sum(
            m.num_bin for m in a.bin_mappers)

    def test_add_data_from_appends_rows(self, rng):
        n = 120
        X = rng.randn(2 * n, 4)
        y = (X[:, 0] > 0).astype(np.float64)
        cfg = Config(max_bin=31)
        half1 = BinnedDataset.construct(X[:n], cfg)
        # second half binned against the SAME mappers (CheckAlign) —
        # the oracle is the full matrix binned with those same mappers
        # (mappers found from different samples legitimately differ)
        half2 = BinnedDataset.construct(X[n:], cfg, reference=half1)
        full = BinnedDataset.construct(X, cfg, reference=half1)
        half1.metadata.set_label(y[:n])
        half2.metadata.set_label(y[n:])
        half1.add_data_from(half2)
        assert half1.num_data == 2 * n
        np.testing.assert_array_equal(half1.bins, full.bins)
        np.testing.assert_allclose(half1.metadata.label, y)

    def test_add_data_from_misaligned_raises(self, rng):
        n = 80
        a = BinnedDataset.construct(rng.randn(n, 3), Config(max_bin=31))
        b = BinnedDataset.construct(rng.randn(n, 4), Config(max_bin=31))
        with pytest.raises(Exception):
            a.add_data_from(b)

    def test_c_api_add_features_from_constructed(self, rng):
        import ctypes

        from lightgbm_tpu import c_api as C

        n = 100
        Xa = rng.randn(n, 3)
        Xb = rng.randn(n, 2)
        ha, hb = ctypes.c_void_p(), ctypes.c_void_p()
        for X, h in ((Xa, ha), (Xb, hb)):
            arr = np.ascontiguousarray(X, np.float64)
            C.LGBM_DatasetCreateFromMat(
                arr.ctypes.data_as(ctypes.c_void_p), C.C_API_DTYPE_FLOAT64,
                np.int32(n), np.int32(X.shape[1]), 1, b"", None,
                ctypes.byref(h))
        out = ctypes.c_int()
        C.LGBM_DatasetGetNumFeature(ha, ctypes.byref(out))
        assert out.value == 3
        # both handles are CONSTRUCTED datasets now
        assert C.LGBM_DatasetAddFeaturesFrom(ha, hb) == 0
        C.LGBM_DatasetGetNumFeature(ha, ctypes.byref(out))
        assert out.value == 5
        C.LGBM_DatasetFree(ha)
        C.LGBM_DatasetFree(hb)


class TestVirtualFileIO:
    """Virtual-file seam (io/file_io.py; reference utils/file_io.h:15-46
    VirtualFileReader/Writer with prefix-dispatched backends)."""

    def test_remote_prefix_without_backend_raises(self):
        from lightgbm_tpu.io.file_io import v_open
        with pytest.raises(OSError, match="register_backend"):
            v_open("hdfs://namenode/data/train.csv")

    def test_registered_backend_feeds_the_parser(self, rng):
        import io as _io

        from lightgbm_tpu.io import file_io
        from lightgbm_tpu.io.parser import load_text_file

        rows = ["%d,%.4f,%.4f" % (int(v[0] > 0), v[0], v[1])
                for v in rng.randn(50, 2)]
        blob = "\n".join(rows) + "\n"
        file_io.register_backend(
            "mem://", lambda path, mode: _io.StringIO(blob))
        try:
            mat, _label, _names = load_text_file("mem://train.csv")
            assert mat.shape == (50, 3)
        finally:
            file_io.unregister_backend("mem://")

    def test_local_paths_unchanged(self, tmp_path):
        from lightgbm_tpu.io.file_io import v_open
        p = tmp_path / "f.txt"
        with v_open(p, "w") as f:
            f.write("ok")
        assert p.read_text() == "ok"


class TestTwoRoundPrePartition:
    """two_round streaming + distributed row pre-partition
    (dataset_loader.cpp:694-740 on the streaming path): every rank bins
    against identical mappers, shards are disjoint, and their union is
    the full dataset."""

    def test_shards_partition_the_file(self, rng, tmp_path):
        from lightgbm_tpu.io.loader import load_two_round
        from lightgbm_tpu.parallel.dist_data import pre_partition_rows

        n = 700
        X = rng.randn(n, 4)
        y = (X[:, 0] > 0).astype(np.float64)
        f = tmp_path / "d.csv"
        np.savetxt(f, np.column_stack([y, X]), delimiter=",", fmt="%.7g")
        cfg = Config(max_bin=31, two_round=True, num_machines=4,
                     data_random_seed=5)
        full = load_two_round(cfg, str(f))
        shards = [load_two_round(cfg, str(f), rank=r, num_machines=4,
                                 pre_partition=True) for r in range(4)]
        assert sum(s.num_data for s in shards) == n
        # shard rows equal the full load's rows at the assignment's
        # indices (same seed -> same draw as the in-memory path)
        for r, s in enumerate(shards):
            keep, _ = pre_partition_rows(n, r, 4, None, seed=5)
            np.testing.assert_array_equal(s.bins, full.bins[keep])
            np.testing.assert_allclose(s.metadata.label,
                                       np.asarray(full.metadata.label)[keep])
            # identical mappers on every rank
            assert ([m.to_state() for m in s.bin_mappers]
                    == [m.to_state() for m in full.bin_mappers])

    def test_query_granular_shards(self, rng, tmp_path):
        from lightgbm_tpu.io.loader import load_two_round

        n, q = 600, 60
        X = rng.randn(n, 3)
        y = rng.randint(0, 3, n).astype(np.float64)
        f = tmp_path / "r.csv"
        np.savetxt(f, np.column_stack([y, X]), delimiter=",", fmt="%.7g")
        np.savetxt(str(f) + ".query", np.full(q, n // q), fmt="%d")
        cfg = Config(max_bin=31, two_round=True, num_machines=3,
                     data_random_seed=9)
        shards = [load_two_round(cfg, str(f), rank=r, num_machines=3,
                                 pre_partition=True) for r in range(3)]
        assert sum(s.num_data for s in shards) == n
        for s in shards:
            qb = s.metadata.query_boundaries
            assert qb is not None and qb[-1] == s.num_data
            # whole queries: every group is the full n//q rows
            np.testing.assert_array_equal(np.diff(qb), n // q)

    def test_stale_side_files_fail_loudly(self, rng, tmp_path):
        # a .query summing short of n (or an oversized .weight) must
        # fatal under pre_partition exactly like the serial path — the
        # sliced vectors would otherwise pass Metadata's validators
        from lightgbm_tpu.io.loader import load_two_round
        n = 300
        X = rng.randn(n, 3)
        y = (X[:, 0] > 0).astype(np.float64)
        f = tmp_path / "s.csv"
        np.savetxt(f, np.column_stack([y, X]), delimiter=",", fmt="%.6g")
        np.savetxt(str(f) + ".query", np.full(5, 10), fmt="%d")  # sums 50
        cfg = Config(max_bin=31, two_round=True, num_machines=2)
        with pytest.raises(Exception, match="query counts"):
            load_two_round(cfg, str(f), rank=0, num_machines=2,
                           pre_partition=True)


class TestFsspecBackend:
    """The fsspec-backed remote backend proves the v_open seam with a
    real (in-memory) filesystem — the working-remote-backend analogue of
    the reference's HDFS client (src/io/file_io.cpp:54-135)."""

    @pytest.fixture(autouse=True)
    def _fsspec_memory(self):
        fsspec = pytest.importorskip("fsspec")
        from lightgbm_tpu.io import file_io
        file_io.enable_fsspec("memory")
        yield fsspec
        file_io.unregister_backend("memory://")
        # wipe the shared in-memory store between tests
        fsspec.filesystem("memory").store.clear()

    def test_text_round_trip(self):
        from lightgbm_tpu.io.file_io import v_open
        with v_open("memory://bucket/hello.txt", "w") as f:
            f.write("42\n")
        with v_open("memory://bucket/hello.txt") as f:
            assert f.read() == "42\n"

    def test_binary_dataset_round_trip(self, rng):
        from lightgbm_tpu.io.dataset import BinnedDataset
        X = rng.randn(200, 5)
        ds = BinnedDataset.construct(X, Config(max_bin=31))
        ds.save_binary("memory://bucket/train.bin")
        back = BinnedDataset.load_binary("memory://bucket/train.bin")
        np.testing.assert_array_equal(np.asarray(ds.bins),
                                      np.asarray(back.bins))
        assert [m.to_state() for m in ds.bin_mappers] == \
               [m.to_state() for m in back.bin_mappers]

    def test_model_save_load_remote(self, rng):
        import lightgbm_tpu as lgb
        X = rng.randn(300, 4)
        y = (X[:, 0] > 0).astype(np.float64)
        bst = lgb.train({"objective": "binary", "verbose": -1},
                        lgb.Dataset(X, y), num_boost_round=5)
        pred = bst.predict(X)
        bst.save_model("memory://models/m.txt")
        back = lgb.Booster(model_file="memory://models/m.txt")
        np.testing.assert_allclose(back.predict(X), pred, rtol=1e-9)
