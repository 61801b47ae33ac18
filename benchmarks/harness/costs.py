"""What the algorithm has to move or compute for one kernel call, from
its shapes alone: the numerator of a roofline share.  The benchmark's own
copies of the two models the first kernel metrics need (lightgbm_tpu's
`_cost_fused_root` in ops/partition_pallas.py and `_cost_predict` in
ops/predict.py), so that a PR which changes a kernel cannot change the
floor it is measured against.  Lower bounds: each operand read once, each
result written once, no padding waste."""

_ARENA_BYTES = 2          # the arena is bfloat16
_PAYLOAD_ROWS = 8         # the 8-row payload group the root pass rewrites


def _padded_features(features):
    """Feature channels of the arena: padded to a multiple of 8."""
    return features + (-features % 8)


def fused_root_bytes(rows, features, max_bin):
    """HBM bytes of the once-per-tree fused refresh + root-histogram pass
    of the int8 path: per row the feature channels and the two fresh code
    planes read, the payload group read and written back; plus the
    [features, max_bin, 3] float32 histogram written."""
    per_row = _ARENA_BYTES * (_padded_features(features) + 2
                              + 2 * _PAYLOAD_ROWS)
    return rows * per_row + features * max_bin * 3 * 4


def predict_matmul_flops(rows, trees, leaves, nodes):
    """FLOPs of the signature match the MXU executes for `rows` rows:
    one multiply-add per (row, tree, leaf, node), i.e. the
    [T, L, N] x [rows, T, N] contraction of ops/predict.py at the padded
    shapes `ensemble_layout` reports."""
    return 2 * rows * trees * leaves * nodes
