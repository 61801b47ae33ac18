"""Fast repeatable A/B harness for training-loop perf work: times N
fused iterations of Higgs-shaped binary training, several repeats,
reports each.

Usage: python tools/train_bench.py [timed_iters] [repeats]
"""
import sys
import time

import numpy as np

sys.path.insert(0, ".")
import lightgbm_tpu as lgb  # noqa: E402


def main():
    iters = int(sys.argv[1]) if len(sys.argv) > 1 else 12
    repeats = int(sys.argv[2]) if len(sys.argv) > 2 else 3
    n, F = 4_000_000, 28
    rng = np.random.default_rng(7)
    X = rng.standard_normal((n, F)).astype(np.float32)
    w = rng.standard_normal(F) / np.sqrt(F)
    logits = X @ w + 0.5 * (X[:, 0] * X[:, 1])
    y = (logits + rng.standard_normal(n) > 0).astype(np.float32)

    params = {"objective": "binary", "num_leaves": 255, "learning_rate": 0.1,
              "max_bin": 255, "verbose": -1, "metric": "none",
              "min_data_in_leaf": 100}
    ds = lgb.Dataset(X, label=y, params=params)
    bst = lgb.Booster(params=params, train_set=ds)
    g = bst._gbdt
    # warm-up: compile + first dispatches
    for _ in range(3):
        bst.update()
    g._sync_model()
    print(f"engine=partition:{g._use_partition_engine} warmed")
    best = None
    for r in range(repeats):
        g._profile_sync()
        t0 = time.time()
        for _ in range(iters):
            bst.update()
        g._sync_model()
        g._profile_sync()
        dt = time.time() - t0
        mrs = n * iters / dt / 1e6
        best = mrs if best is None else max(best, mrs)
        print(f"rep{r}: {dt/iters*1000:.1f} ms/iter  {mrs:.2f} Mrows*iter/s")
    print(f"BEST: {best:.2f} Mrows*iter/s  (vs_baseline {best/22.01:.3f})")


if __name__ == "__main__":
    main()
