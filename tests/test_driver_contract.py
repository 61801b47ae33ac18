"""bench.py's two workloads still run end to end.  `bench.main()` refuses to
train off a TPU (tests/test_chip_smoke.py pins that), so the CPU contract
calls the workload functions directly at their smoke shapes.  The driver's
own check is `python chip_smoke.py` on the chip; `__graft_entry__.py`, which
the previous driver called, is gone."""
import pytest

pytestmark = pytest.mark.slow


def test_bench_workloads_run_on_cpu():
    import jax
    import jax.numpy as jnp

    import bench
    import lightgbm_tpu as lgb

    sync = bench._make_sync(jax, jnp)
    higgs = bench.bench_higgs(lgb, sync, False, quantized=True)
    rank = bench.bench_lambdarank(lgb, sync, False)
    assert higgs["quality_ok"] and rank["quality_ok"], (higgs, rank)
    for out in (higgs, rank):
        assert out["throughput_mrows_iter_s"] > 0
        # off the TPU `auto` is the label engine; main() would call that
        # a failed run on the chip
        assert out["engine"] == "label"
    assert higgs["quantized_active"] is False
