"""The table of peaks and the two cost functions, against shapes worked
out by hand."""
import pytest

from benchmarks.harness import costs, peaks


def test_v5e_peaks_are_the_published_ones_with_their_source():
    p = peaks.peaks_of("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flop_per_s"] == 197e12
    assert p["int8_op_per_s"] == 393e12
    assert "Google Cloud" in p["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
def test_an_unknown_device_is_an_error_not_a_default(kind):
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_of(kind)


def test_fused_root_bytes_at_the_higgs_shape():
    # 28 features pad to 32 channels; + 2 code planes + the 8-row payload
    # group read and written = 50 bfloat16 = 100 B per row (PERF.md, PR 21)
    rows = 10_500_000
    assert costs.fused_root_bytes(rows, 28, 255) \
        == rows * 100 + 28 * 255 * 3 * 4


def test_fused_root_bytes_pads_features_to_eight():
    assert costs.fused_root_bytes(1, 137, 255) \
        == 2 * (144 + 2 + 16) + 137 * 255 * 12


def test_predict_matmul_flops_at_the_500_tree_shape():
    # 500 trees pad to T=512, 254 nodes, 256 leaf slots: 66.6 MFLOP a row
    per_row = costs.predict_matmul_flops(1, 512, 256, 254)
    assert per_row == 2 * 512 * 256 * 254 == 66_584_576
    assert costs.predict_matmul_flops(262_144, 512, 256, 254) \
        == 262_144 * per_row
