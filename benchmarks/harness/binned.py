"""The training set as the system's binned Dataset, through a cache.

Binning 10.5M rows on the host is the longest part of a cold set-up, and
the columns are a function of the configuration alone (data/higgs.py), so
the first run of a configuration in a checkout bins and saves with
`Dataset.save_binary` — the reference project's documented binary-dataset
feature — and later runs load the file the way
`c_api.LGBM_DatasetCreateFromFile` does: `BinnedDataset.load_binary`, then
a `Dataset` shell around it.  (The Python `Dataset("x.bin")` path does not
recognise the binary file today; PERF.md lists that for a later PR.)  The
label, which `--seed` draws, is set on the loaded set every time.
tests/benchmark pins that both routes grow the same trees.
"""
import os


def dataset_params(params):
    """The parameters binning reads."""
    return {k: params[k] for k in ("max_bin", "verbose") if k in params}


def fresh(lgb, X, y, group, params):
    ds = lgb.Dataset(X, y, group=group, params=dataset_params(params))
    ds.construct()
    return ds


def load(lgb, path, y, group, params):
    from lightgbm_tpu.io.dataset import BinnedDataset
    ds = lgb.Dataset(None, params=dataset_params(params))
    ds._binned = BinnedDataset.load_binary(path)
    ds.set_label(y)
    if group is not None:
        ds.set_group(group)
    return ds


def cached(bench, lgb, X, y, group, params, key):
    """(Dataset, whether it came from the cache)."""
    path = bench.cache_path("binned", key + ".bin")
    if os.path.exists(path):
        return load(lgb, path, y, group, params), True
    ds = fresh(lgb, X, y, group, params)
    # written under another name first: a run that is killed, or two
    # that start together, never leave half a file under the real one
    tmp = "%s.%d.tmp" % (path, os.getpid())
    ds.save_binary(tmp)
    os.replace(tmp, path)
    return ds, False
