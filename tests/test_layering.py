"""What PR 30 took out stays out: the kernel layer does not know the
observability layer, nothing in the library reaches into tools/, the six
measurement options are unknown parameters, and the two seeded generators
that moved (bench.py -> chip_smoke.py, /root/reference -> tests/_fixtures.py)
give the bytes they gave."""
import ast
import hashlib
import os
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "lightgbm_tpu")


def _imports(path, package):
    """Absolute dotted names of everything `path` imports, at any depth:
    'a.b' for `import a.b`, 'a.b.c' for `from a.b import c`."""
    with open(path) as fh:
        tree = ast.parse(fh.read(), path)
    found = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            found += [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                parts = package.split(".")
                parts = parts[:len(parts) - node.level + 1]
                base = ".".join(parts + ([base] if base else []))
            found += ["%s.%s" % (base, a.name) for a in node.names]
    return found


@pytest.mark.parametrize("layer", ["ops", "io", "models"])
def test_layer_imports_no_measurement_code(layer):
    root = os.path.join(PKG, layer)
    files = sorted(f for f in os.listdir(root) if f.endswith(".py"))
    assert files
    bad = []
    for name in files:
        for imp in _imports(os.path.join(root, name),
                            "lightgbm_tpu." + layer):
            if (imp.startswith("lightgbm_tpu.obs.perf")
                    or imp.endswith(".StepDecomposer")
                    or imp == "tools" or imp.startswith("tools.")
                    or (layer == "ops"
                        and imp.startswith("lightgbm_tpu.obs"))):
                bad.append("%s/%s imports %s" % (layer, name, imp))
    assert not bad, bad


@pytest.mark.parametrize("name,value", [
    ("tpu_perf_roofline", True), ("tpu_perf_chain", 8),
    ("tpu_perf_gate_tolerance", 0.15), ("tpu_scaling_decomp", True),
    ("tpu_scaling_window", 8), ("tpu_scaling_ici_gbps", 45.0)])
def test_removed_option_is_an_unknown_parameter(name, value, capsys):
    rng = np.random.RandomState(0)
    X = rng.rand(200, 4)
    y = (X[:, 0] > 0.5).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 4, "verbose": 0,
              name: value}
    bst = lgb.train(params, lgb.Dataset(X, y), num_boost_round=2)
    assert "Unknown parameter: %s" % name in capsys.readouterr().err
    assert bst.num_trees() == 2
    assert ((bst.predict(X) > 0.5) == (y > 0)).mean() > 0.9


def _sha(arrays):
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


# taken from bench.py at f5e845d (the parent of PR 30) by this same _sha
@pytest.mark.parametrize("call,args,want", [
    ("higgs_data", (4096, 64),
     "f26e18452c4c280224f0cc73585bc45ef0aa461452a9f6fe2225e875f1b11dc7"),
    ("mslr_data", (8,),
     "5c040920754849a4934d8ec385a63de00f1d8e706eb8ee2cf00f51595f77afbd")])
def test_chip_smoke_generators_give_bench_py_bytes(call, args, want):
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    assert _sha(getattr(chip_smoke, call)(*args)) == want


def test_example_files_are_reproducible(example_files, tmp_path):
    from _fixtures import FILES, SEED, write_examples
    again = write_examples(tmp_path, SEED)
    assert sorted(again) == sorted(example_files) == sorted(FILES)
    for name in FILES:
        with open(example_files[name], "rb") as a, \
                open(again[name], "rb") as b:
            assert a.read() == b.read(), name
    other = write_examples(tmp_path, SEED + 1)
    with open(example_files["binary.train"], "rb") as a, \
            open(other["binary.train"], "rb") as b:
        assert a.read() != b.read()
