"""Reads a LightGBM v2 model text and scores raw feature rows by walking
each tree (Tree::Predict / NumericalDecision of the reference project's
tree.h), in numpy float64.  Independent of lightgbm_tpu: the benchmark
compares `Booster.predict` with it on the saved model text.

Numerical splits only (neither configuration has a categorical column):
a model with `num_cat` above 0 is refused.
"""
import numpy as np

_DEFAULT_LEFT = 2
_ZERO_THRESHOLD = 1e-35
_MISSING_ZERO, _MISSING_NAN = 1, 2


def parse_model(text):
    """(header, trees): the header's key=value pairs and, per tree, its
    arrays by their names in the text."""
    head, _, rest = text.partition("Tree=")
    header = dict(line.split("=", 1) for line in head.splitlines()
                  if "=" in line)
    trees = []
    for block in rest.split("Tree=") if rest else []:
        block = block.split("end of trees")[0]
        kv = dict(line.split("=", 1) for line in block.splitlines()[1:]
                  if "=" in line)
        if int(kv.get("num_cat", 0)):
            raise ValueError("categorical splits are not covered")
        tree = {"num_leaves": int(kv["num_leaves"])}
        for key, kind in (("split_feature", np.int64),
                          ("threshold", np.float64),
                          ("decision_type", np.int64),
                          ("left_child", np.int64),
                          ("right_child", np.int64),
                          ("leaf_value", np.float64)):
            if key in kv:
                tree[key] = np.array(kv[key].split(), np.float64).astype(kind)
        trees.append(tree)
    return header, trees


def _walk(tree, X):
    """Leaf value of every row of X for one tree."""
    if tree["num_leaves"] <= 1:
        return np.full(len(X), tree["leaf_value"][0])
    node = np.zeros(len(X), np.int64)           # >= 0: internal, < 0: ~leaf
    rows = np.arange(len(X))
    while len(rows):
        nd = node[rows]
        v = X[rows, tree["split_feature"][nd]]
        kind = tree["decision_type"][nd]
        missing_type = (kind >> 2) & 3
        nan = np.isnan(v)
        v = np.where(nan & (missing_type != _MISSING_NAN), 0.0, v)
        missing = (((missing_type == _MISSING_ZERO)
                    & (np.abs(v) <= _ZERO_THRESHOLD))
                   | ((missing_type == _MISSING_NAN) & nan))
        left = np.where(missing, (kind & _DEFAULT_LEFT) > 0,
                        v <= tree["threshold"][nd])
        node[rows] = np.where(left, tree["left_child"][nd],
                              tree["right_child"][nd])
        rows = rows[node[rows] >= 0]
    return tree["leaf_value"][~node]


def _sum_of_trees(header, trees, X, num_trees):
    if int(header.get("num_tree_per_iteration", 1)) != 1:
        raise ValueError("one tree per iteration only")
    X = np.asarray(X, np.float64)
    out = np.zeros(len(X))
    for tree in trees[:num_trees]:
        out += _walk(tree, X)
    return out


def raw_scores(text, X, num_trees=None):
    """Sum of the first `num_trees` trees' outputs per row (one class)."""
    return _sum_of_trees(*parse_model(text), X, num_trees)


def predict(text, X, num_trees=None):
    """What `Booster.predict` returns: the sigmoid of the raw score for a
    binary model, the raw score otherwise."""
    header, trees = parse_model(text)
    raw = _sum_of_trees(header, trees, X, num_trees)
    objective = header.get("objective", "").split()
    if objective and objective[0] == "binary":
        sigmoid = 1.0
        for tok in objective[1:]:
            if tok.startswith("sigmoid:"):
                sigmoid = float(tok.split(":", 1)[1])
        return 1.0 / (1.0 + np.exp(-sigmoid * raw))
    return raw
