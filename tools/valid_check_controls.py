"""What higgs-valid.train's check reads when validation is scored wrongly:
the control readings behind `correct.valid_series` of
benchmarks/configs/higgs-binary-int8-valid.json.

    JAX_PLATFORMS=cpu python tools/valid_check_controls.py [train_rows] [trees]

Trains a 255-leaf model on the configuration's data at a reduced row count
(any backend: only the model text is used), walks the configuration's
500 000 test rows through it with the benchmark's plain float64 walker, and
prints, iteration by iteration, how far the reference's float64 AUC and raw
scores move when the accumulated scores are (a) kept in float32, as the
system keeps them, (b) kept in bfloat16, (c) given another leaf's value on
one row in a thousand of each tree, (d) given one tree's leaf values ten
times over (a leaf off by the shrinkage); and the boost_from_average bias,
which no AUC sees.  A computation with numpy, not a device measurement.
"""
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(train_rows=400_000, trees=40):
    import ml_dtypes
    import lightgbm_tpu as lgb
    from benchmarks.harness import manifest
    from benchmarks.reference import metrics, walker
    cfg = manifest.load_json(ROOT, "benchmarks", "configs",
                             "higgs-binary-int8-valid.json")
    gen = manifest.load_module(ROOT, "data", cfg["data"]["generator"])
    args, seed = cfg["data"]["args"], 2147483777
    X = gen.features(args, "train", train_rows)
    y, _ = gen.labels(args, seed, "train", X)
    rate = float(y.mean())
    print("base rate %.5f, bias %.3e" % (rate, np.log(rate / (1 - rate))))
    Xt = gen.features(args, cfg["valid"]["part"], cfg["valid"]["rows"])
    yt, _ = gen.labels(args, seed, cfg["valid"]["part"], Xt)
    params = {k: v for k, v in cfg["params"].items()
              if not k.startswith("tpu_")}
    text = lgb.train(params, lgb.Dataset(X, y),
                     num_boost_round=trees).model_to_string()
    _, model = walker.parse_model(text)
    Xt = np.asarray(Xt, np.float64)
    rng = np.random.default_rng(0)
    raw, wrong, tenfold = (np.zeros(len(yt)) for _ in range(3))
    f32, bf16 = (np.zeros(len(yt), np.float32) for _ in range(2))
    print("iter  auc       |auc - ref|: f32  bf16  1/1000 rows  x10 tree"
          "   max |score - ref|: f32  bf16  1/1000 rows")
    for t, tree in enumerate(model):
        d = walker._walk(tree, Xt)
        raw += d
        f32 = f32 + d.astype(np.float32)
        bf16 = (bf16 + d.astype(np.float32)).astype(
            ml_dtypes.bfloat16).astype(np.float32)
        moved = d.copy()
        rows = rng.choice(len(yt), len(yt) // 1000, replace=False)
        moved[rows] = tree["leaf_value"][
            rng.integers(0, tree["num_leaves"], len(rows))]
        wrong += moved
        tenfold += d * (10.0 if t == 5 else 1.0)
        ref = metrics.auc(yt, raw)
        print("%4d  %.6f  %.2e  %.2e  %.2e  %.2e   %.2e  %.2e  %.2e" % (
            t, ref, *(abs(metrics.auc(yt, s) - ref)
                      for s in (f32, bf16, wrong, tenfold)),
            *(float(np.abs(s - raw).max()) for s in (f32, bf16, wrong))),
            flush=True)


if __name__ == "__main__":
    main(*(int(a) for a in sys.argv[1:3]))
