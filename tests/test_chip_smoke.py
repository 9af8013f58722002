"""`chip_smoke.py`'s phases at a tiny size on the CPU (kernel interpreted).

The chip runs them at full size; here they prove the control flow and
the checks: zero divergences and mismatches, the kernel route taken, and
the script refusing to run without a TPU."""
import importlib.util
import os

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_main_refuses_without_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert out == "" and "needs a TPU" in err


def test_pallas_phase_takes_the_kernel_route(smoke):
    from repro.kernels.ops import ROUTE_KERNEL, ROUTE_KERNEL_RECHECK
    out = smoke.phase_pallas(budget=96 * 1024, probe_n=1024,
                             n_queries=4096, batch=1024)
    assert out["n_keys"] <= 5000
    assert out["mismatches_vs_local"] == 0
    assert out["mismatches_vs_oracle"] == 0
    routes = out["routes"]
    assert set(routes) <= {ROUTE_KERNEL, ROUTE_KERNEL_RECHECK}
    assert sum(routes.values()) == 4096 // 1024
    assert out["recheck_patched_lanes"] == 0


def test_served_phase_replays_without_divergence(smoke):
    out = smoke.phase_served(n_keys=5000, n_ops=12288, n_range_ops=512,
                             rate=50000.0)
    assert out["divergences"] == 0 and out["failed_ops"] == 0
    assert out["merges"] >= 1 and out["retraces"] == 0
    assert out["maint_errors"] == 0 and not out["maint_degraded"]


def test_sharded_phase_matches_oracle(smoke):
    results = smoke.phase_sharded(n_keys=5000, n_chips=1, n_queries=2048,
                                  n_ranges=256, n_writes=256)
    assert set(results) == {f"{stage},{check}"
                            for stage in ("built", "pending", "flushed")
                            for check in ("gather", "a2a", "range")}
    assert not any(results.values())
