"""C API shim tests — the reference's tests/c_api_test/test_.py flow
driven against lightgbm_tpu.c_api as the LIB."""
import ctypes
import os

import numpy as np
import pytest

import lightgbm_tpu.c_api as LIB


def c_array(ctype, values):
    return (ctype * len(values))(*values)


def c_str(string):
    return ctypes.c_char_p(string.encode("ascii"))


def _load_from_file(filename, reference):
    handle = ctypes.c_void_p()
    rc = LIB.LGBM_DatasetCreateFromFile(
        c_str(filename), c_str("max_bin=15"), reference,
        ctypes.byref(handle))
    assert rc == 0, LIB.LGBM_GetLastError()
    return handle


def _read_mat(filename):
    data, label = [], []
    with open(filename) as inp:
        for line in inp.readlines():
            data.append([float(x) for x in line.split("\t")[1:]])
            label.append(float(line.split("\t")[0]))
    return np.array(data), np.array(label, dtype=np.float32)


def _load_from_mat(filename, reference):
    mat, label = _read_mat(filename)
    flat = np.array(mat.reshape(mat.size), copy=False)
    handle = ctypes.c_void_p()
    rc = LIB.LGBM_DatasetCreateFromMat(
        flat.ctypes.data_as(ctypes.POINTER(ctypes.c_void_p)),
        LIB.C_API_DTYPE_FLOAT64, mat.shape[0], mat.shape[1], 1,
        c_str("max_bin=15"), reference, ctypes.byref(handle))
    assert rc == 0, LIB.LGBM_GetLastError()
    rc = LIB.LGBM_DatasetSetField(handle, c_str("label"),
                                  c_array(ctypes.c_float, label),
                                  len(label), 0)
    assert rc == 0, LIB.LGBM_GetLastError()
    return handle


def test_dataset_roundtrip(tmp_path, example_files):
    binary_train = example_files["binary.train"]
    binary_test = example_files["binary.test"]
    from scipy import sparse
    train = _load_from_file(binary_train, None)
    num_data = ctypes.c_long()
    assert LIB.LGBM_DatasetGetNumData(train, ctypes.byref(num_data)) == 0
    assert num_data.value == 7000
    num_feature = ctypes.c_long()
    assert LIB.LGBM_DatasetGetNumFeature(train,
                                         ctypes.byref(num_feature)) == 0
    assert num_feature.value == 28

    # mat / CSR / CSC against the train reference
    test = _load_from_mat(binary_test, train)
    LIB.LGBM_DatasetFree(test)
    mat, label = _read_mat(binary_test)
    for maker, args in (("CSR", sparse.csr_matrix(mat)),
                        ("CSC", sparse.csc_matrix(mat))):
        m = args
        handle = ctypes.c_void_p()
        if maker == "CSR":
            rc = LIB.LGBM_DatasetCreateFromCSR(
                c_array(ctypes.c_int, m.indptr), LIB.C_API_DTYPE_INT32,
                c_array(ctypes.c_int, m.indices),
                m.data.ctypes.data_as(ctypes.POINTER(ctypes.c_void_p)),
                LIB.C_API_DTYPE_FLOAT64, len(m.indptr), len(m.data),
                m.shape[1], c_str("max_bin=15"), train,
                ctypes.byref(handle))
        else:
            rc = LIB.LGBM_DatasetCreateFromCSC(
                c_array(ctypes.c_int, m.indptr), LIB.C_API_DTYPE_INT32,
                c_array(ctypes.c_int, m.indices),
                m.data.ctypes.data_as(ctypes.POINTER(ctypes.c_void_p)),
                LIB.C_API_DTYPE_FLOAT64, len(m.indptr), len(m.data),
                m.shape[0], c_str("max_bin=15"), train,
                ctypes.byref(handle))
        assert rc == 0, (maker, LIB.LGBM_GetLastError())
        rc = LIB.LGBM_DatasetSetField(handle, c_str("label"),
                                      c_array(ctypes.c_float, label),
                                      len(label), 0)
        assert rc == 0
        nd = ctypes.c_long()
        LIB.LGBM_DatasetGetNumData(handle, ctypes.byref(nd))
        assert nd.value == 500
        LIB.LGBM_DatasetFree(handle)

    # save-binary round trip (auto-detected on load, dataset_loader.cpp:267)
    binpath = str(tmp_path / "train.binary.bin")
    assert LIB.LGBM_DatasetSaveBinary(train, c_str(binpath)) == 0
    LIB.LGBM_DatasetFree(train)
    train2 = _load_from_file(binpath, None)
    nd = ctypes.c_long()
    LIB.LGBM_DatasetGetNumData(train2, ctypes.byref(nd))
    assert nd.value == 7000
    LIB.LGBM_DatasetFree(train2)


def test_booster_train_eval_save_predict(tmp_path, example_files):
    binary_train = example_files["binary.train"]
    binary_test = example_files["binary.test"]
    train = _load_from_mat(binary_train, None)
    test = _load_from_mat(binary_test, train)
    booster = ctypes.c_void_p()
    rc = LIB.LGBM_BoosterCreate(
        train, c_str("app=binary metric=auc num_leaves=31 verbose=-1"),
        ctypes.byref(booster))
    assert rc == 0, LIB.LGBM_GetLastError()
    assert LIB.LGBM_BoosterAddValidData(booster, test) == 0
    is_finished = ctypes.c_int(0)
    aucs = []
    for i in range(1, 31):
        assert LIB.LGBM_BoosterUpdateOneIter(
            booster, ctypes.byref(is_finished)) == 0
        result = np.array([0.0], dtype=np.float64)
        out_len = ctypes.c_ulong(0)
        rc = LIB.LGBM_BoosterGetEval(
            booster, 1, ctypes.byref(out_len),
            result.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
        assert rc == 0 and out_len.value == 1
        aucs.append(result[0])
    # valid-set AUC with max_bin=15 on the generated pair
    # (tests/_fixtures.py): measured 0.7927 after 30 rounds, 0.6802 after
    # the first (label engine, CPU, PR 30); the floor leaves the 0.02 that
    # equally valid f32 accumulation orders may differ by
    assert aucs[-1] > 0.77 and aucs[-1] > aucs[0]

    model_path = str(tmp_path / "model.txt")
    assert LIB.LGBM_BoosterSaveModel(booster, 0, -1, c_str(model_path)) == 0
    LIB.LGBM_BoosterFree(booster)
    LIB.LGBM_DatasetFree(train)
    LIB.LGBM_DatasetFree(test)

    booster2 = ctypes.c_void_p()
    num_total_model = ctypes.c_long()
    rc = LIB.LGBM_BoosterCreateFromModelfile(
        c_str(model_path), ctypes.byref(num_total_model),
        ctypes.byref(booster2))
    assert rc == 0 and num_total_model.value == 30

    mat, label = _read_mat(binary_test)
    flat = np.array(mat.reshape(mat.size), copy=False)
    preb = np.zeros(mat.shape[0], dtype=np.float64)
    num_preb = ctypes.c_long()
    rc = LIB.LGBM_BoosterPredictForMat(
        booster2, flat.ctypes.data_as(ctypes.POINTER(ctypes.c_void_p)),
        LIB.C_API_DTYPE_FLOAT64, mat.shape[0], mat.shape[1], 1,
        LIB.C_API_PREDICT_RAW_SCORE, 25, c_str(""),
        ctypes.byref(num_preb),
        preb.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
    assert rc == 0 and num_preb.value == mat.shape[0]
    assert np.abs(preb).max() > 0

    out_file = str(tmp_path / "preb.txt")
    rc = LIB.LGBM_BoosterPredictForFile(
        booster2, c_str(binary_test), 0, 0, 25, c_str(""), c_str(out_file))
    assert rc == 0
    vals = np.loadtxt(out_file)
    assert vals.shape == (500,)
    assert ((vals >= 0) & (vals <= 1)).all()     # normal = probabilities
    from sklearn.metrics import roc_auc_score
    assert roc_auc_score(label, vals) > 0.77   # measured 0.7896 (25 trees)
    LIB.LGBM_BoosterFree(booster2)


def _mat_dataset(rng, n=400, f=6, label=True, params="max_bin=31"):
    X = rng.rand(n, f)
    h = ctypes.c_void_p()
    flat = np.ascontiguousarray(X.reshape(-1))
    assert LIB.LGBM_DatasetCreateFromMat(
        flat.ctypes.data_as(ctypes.POINTER(ctypes.c_void_p)), 1, n, f, 1,
        c_str(params), None, ctypes.byref(h)) == 0
    if label:
        y = (X[:, 0] > 0.5).astype(np.float32)
        assert LIB.LGBM_DatasetSetField(
            h, c_str("label"), c_array(ctypes.c_float, y), n, 0) == 0
    return h, X


def test_streaming_push_rows(rng):
    n, f = 300, 5
    h = ctypes.c_void_p()
    assert LIB.LGBM_DatasetCreateFromSampledColumn(
        None, None, f, None, 50, n, c_str("max_bin=15"),
        ctypes.byref(h)) == 0
    X = rng.rand(n, f)
    half = n // 2
    for start, block in ((0, X[:half]), (half, X[half:])):
        flat = np.ascontiguousarray(block.reshape(-1))
        assert LIB.LGBM_DatasetPushRows(
            h, flat.ctypes.data_as(ctypes.POINTER(ctypes.c_void_p)), 1,
            len(block), f, start) == 0
    y = (X[:, 0] > 0.5).astype(np.float32)
    assert LIB.LGBM_DatasetSetField(
        h, c_str("label"), c_array(ctypes.c_float, y), n, 0) == 0
    nd = ctypes.c_long()
    assert LIB.LGBM_DatasetGetNumData(h, ctypes.byref(nd)) == 0
    assert nd.value == n
    # pushed rows must train
    bst = ctypes.c_void_p()
    assert LIB.LGBM_BoosterCreate(
        h, c_str("objective=binary verbose=-1 min_data_in_leaf=5"),
        ctypes.byref(bst)) == 0
    fin = ctypes.c_int()
    assert LIB.LGBM_BoosterUpdateOneIter(bst, ctypes.byref(fin)) == 0


def test_push_rows_by_csr(rng):
    """Streaming ingest: mappers fitted from the sampled columns, pushed
    rows binned incrementally — the binned result must match a dataset
    constructed from the same rows with mappers from the same sample."""
    from scipy import sparse
    n, f, s = 200, 6, 50
    X = (rng.rand(n, f) * (rng.rand(n, f) > 0.5)).astype(np.float64)
    csr = sparse.csr_matrix(X)
    h = ctypes.c_void_p()
    # dense per-column sample of the first s rows (the reference's
    # sampled-column format: values + row indices per column)
    col_vals = [np.ascontiguousarray(X[:s, j]) for j in range(f)]
    col_idx = [np.arange(s, dtype=np.int32) for _ in range(f)]
    vp = (ctypes.c_void_p * f)(*[v.ctypes.data_as(ctypes.c_void_p).value
                                 for v in col_vals])
    ip = (ctypes.c_void_p * f)(*[v.ctypes.data_as(ctypes.c_void_p).value
                                 for v in col_idx])
    npc = (ctypes.c_int32 * f)(*([s] * f))
    assert LIB.LGBM_DatasetCreateFromSampledColumn(
        vp, ip, f, npc, s, n, c_str("max_bin=15"),
        ctypes.byref(h)) == 0
    assert LIB.LGBM_DatasetPushRowsByCSR(
        h, c_array(ctypes.c_int, csr.indptr), 2,
        c_array(ctypes.c_int, csr.indices),
        csr.data.ctypes.data_as(ctypes.POINTER(ctypes.c_void_p)), 1,
        len(csr.indptr), len(csr.data), f, 0) == 0
    ds = LIB._resolve(h)
    # no O(n*f) float staging: the raw matrix must NOT exist
    assert ds.data is None
    ds.construct()
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    oracle_m = BinnedDataset.construct(X[:s], Config({"max_bin": 15}),
                                       bin_rows=False)
    np.testing.assert_array_equal(np.asarray(ds._binned.bins),
                                  oracle_m.bin_block(X))


def test_subset_and_feature_names(rng):
    h, X = _mat_dataset(rng)
    names = (ctypes.c_char_p * 6)(*[("f%d" % i).encode() for i in range(6)])
    assert LIB.LGBM_DatasetSetFeatureNames(h, names, 6) == 0
    idx = np.arange(0, 100, dtype=np.int32)
    sub = ctypes.c_void_p()
    assert LIB.LGBM_DatasetGetSubset(
        h, c_array(ctypes.c_int32, idx), len(idx), c_str(""),
        ctypes.byref(sub)) == 0
    nd = ctypes.c_long()
    assert LIB.LGBM_DatasetGetNumData(sub, ctypes.byref(nd)) == 0
    assert nd.value == 100
    bufs = [ctypes.create_string_buffer(64) for _ in range(6)]
    arr = (ctypes.c_char_p * 6)(*[ctypes.cast(b, ctypes.c_char_p)
                                  for b in bufs])
    out_len = ctypes.c_int()
    assert LIB.LGBM_DatasetGetFeatureNames(
        h, arr, ctypes.byref(out_len)) == 0
    assert out_len.value == 6 and bufs[0].value == b"f0"


def test_booster_breadth(rng):
    h, X = _mat_dataset(rng)
    bst = ctypes.c_void_p()
    assert LIB.LGBM_BoosterCreate(
        h, c_str("objective=binary verbose=-1 min_data_in_leaf=5"),
        ctypes.byref(bst)) == 0
    fin = ctypes.c_int()
    for _ in range(3):
        assert LIB.LGBM_BoosterUpdateOneIter(bst, ctypes.byref(fin)) == 0
    # custom-gradient update
    n = len(X)
    pred = np.zeros(n, np.float64)
    grad = np.asarray(pred - (X[:, 0] > 0.5), np.float32)
    hess = np.full(n, 0.25, np.float32)
    assert LIB.LGBM_BoosterUpdateOneIterCustom(
        bst, c_array(ctypes.c_float, grad), c_array(ctypes.c_float, hess),
        ctypes.byref(fin)) == 0
    # counters
    out = ctypes.c_long()
    assert LIB.LGBM_BoosterNumberOfTotalModel(bst, ctypes.byref(out)) == 0
    assert out.value == 4
    assert LIB.LGBM_BoosterNumModelPerIteration(bst, ctypes.byref(out)) == 0
    assert out.value == 1
    assert LIB.LGBM_BoosterGetNumFeature(bst, ctypes.byref(out)) == 0
    assert out.value == 6
    # leaf get/set round trip
    lv = ctypes.c_double()
    assert LIB.LGBM_BoosterGetLeafValue(bst, 0, 0, ctypes.byref(lv)) == 0
    assert LIB.LGBM_BoosterSetLeafValue(bst, 0, 0, lv.value * 2.0) == 0
    lv2 = ctypes.c_double()
    assert LIB.LGBM_BoosterGetLeafValue(bst, 0, 0, ctypes.byref(lv2)) == 0
    assert abs(lv2.value - lv.value * 2.0) < 1e-12
    # importance
    imp = np.zeros(6, np.float64)
    assert LIB.LGBM_BoosterFeatureImportance(
        bst, -1, 0, imp.ctypes.data_as(ctypes.POINTER(ctypes.c_double))) == 0
    assert imp.sum() > 0
    # dump model JSON
    out_len = ctypes.c_long()
    buf = ctypes.create_string_buffer(1 << 20)
    assert LIB.LGBM_BoosterDumpModel(
        bst, 0, -1, len(buf.raw), ctypes.byref(out_len), buf) == 0
    import json
    d = json.loads(buf.value.decode())
    assert d["tree_info"]
    # calc num predict
    assert LIB.LGBM_BoosterCalcNumPredict(
        bst, 10, 0, -1, ctypes.byref(out_len)) == 0
    assert out_len.value == 10
    # predict for mats (array of row pointers)
    rows = [np.ascontiguousarray(X[i]) for i in range(4)]
    ptrs = (ctypes.POINTER(ctypes.c_double) * 4)(
        *[r.ctypes.data_as(ctypes.POINTER(ctypes.c_double)) for r in rows])
    res = np.zeros(4, np.float64)
    assert LIB.LGBM_BoosterPredictForMats(
        bst, ptrs, 1, 4, 6, 0, -1, c_str(""), ctypes.byref(out_len),
        res.ctypes.data_as(ctypes.POINTER(ctypes.c_double))) == 0
    assert out_len.value == 4
    # reset parameter
    assert LIB.LGBM_BoosterResetParameter(
        bst, c_str("learning_rate=0.05")) == 0
    assert abs(LIB._resolve(bst)._gbdt.shrinkage_rate - 0.05) < 1e-12
    # refit with leaf preds
    lp = np.zeros((n, 4), np.int32)
    assert LIB.LGBM_BoosterRefit(
        bst, lp.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), n, 4) == 0
    # merge
    bst2 = ctypes.c_void_p()
    assert LIB.LGBM_BoosterCreate(
        h, c_str("objective=binary verbose=-1 min_data_in_leaf=5"),
        ctypes.byref(bst2)) == 0
    assert LIB.LGBM_BoosterUpdateOneIter(bst2, ctypes.byref(fin)) == 0
    assert LIB.LGBM_BoosterMerge(bst, bst2) == 0
    assert LIB.LGBM_BoosterNumberOfTotalModel(bst, ctypes.byref(out)) == 0
    assert out.value == 5


def test_dataset_dump_text(rng, tmp_path):
    h, _ = _mat_dataset(rng, n=50)
    p = tmp_path / "dump.txt"
    assert LIB.LGBM_DatasetDumpText(h, c_str(str(p))) == 0
    text = p.read_text()
    assert text.startswith("num_data: 50")


def test_set_last_error():
    assert LIB.LGBM_SetLastError(b"custom boom") == 0
    assert LIB.LGBM_GetLastError() == b"custom boom"


def test_eval_counts_names_values_align_for_multivalue_metrics(rng):
    """GetEvalCounts == len(GetEvalNames) == len(GetEval results) even
    for metrics that expand to one value per position (ndcg@k / map@k)
    — the reference sums Metric::GetName() sizes (metric.hpp), and a
    mismatch overflows fixed-size caller buffers (the R glue sizes its
    output from GetEvalCounts)."""
    n, q = 600, 6
    X = rng.rand(n, 5)
    h = ctypes.c_void_p()
    flat = np.ascontiguousarray(X.reshape(-1))
    assert LIB.LGBM_DatasetCreateFromMat(
        flat.ctypes.data_as(ctypes.POINTER(ctypes.c_void_p)), 1, n, 5, 1,
        c_str("max_bin=31"), None, ctypes.byref(h)) == 0
    y = rng.randint(0, 3, n).astype(np.float32)
    assert LIB.LGBM_DatasetSetField(
        h, c_str("label"), c_array(ctypes.c_float, y), n, 0) == 0
    grp = np.full(q, n // q, np.int32)
    assert LIB.LGBM_DatasetSetField(
        h, c_str("group"),
        grp.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), q, 2) == 0
    bst = ctypes.c_void_p()
    assert LIB.LGBM_BoosterCreate(
        h, c_str("objective=lambdarank metric=ndcg,map verbose=-1"),
        ctypes.byref(bst)) == 0
    fin = ctypes.c_int(0)
    assert LIB.LGBM_BoosterUpdateOneIter(bst, ctypes.byref(fin)) == 0

    cnt = ctypes.c_int(0)
    assert LIB.LGBM_BoosterGetEvalCounts(bst, ctypes.byref(cnt)) == 0
    assert cnt.value == 10  # ndcg@1..5 + map@1..5
    bufs = [ctypes.create_string_buffer(256) for _ in range(cnt.value)]
    arr = (ctypes.c_char_p * cnt.value)(
        *[ctypes.addressof(b) for b in bufs])
    nn = ctypes.c_int(0)
    assert LIB.LGBM_BoosterGetEvalNames(bst, ctypes.byref(nn), arr) == 0
    names = [bufs[i].value.decode() for i in range(nn.value)]
    assert names[:5] == ["ndcg@%d" % k for k in range(1, 6)]
    vals = (ctypes.c_double * cnt.value)()
    vn = ctypes.c_int(0)
    assert LIB.LGBM_BoosterGetEval(bst, 0, ctypes.byref(vn), vals) == 0
    assert vn.value == nn.value == cnt.value
