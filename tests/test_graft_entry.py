"""Driver-gate regression tests.

The driver invokes ``__graft_entry__.dryrun_multichip(n)`` in a fresh process
whose ambient environment may ask for the TPU (``JAX_PLATFORMS=tpu`` on a chip
machine).  Rounds 1 and 2 both failed this gate (mesh reshape crash; then eager
arrays landing on the default backend instead of the CPU mesh).  These tests
run the entry points in subprocesses that reproduce the driver's environment
shapes, so the gate can never silently regress again.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.mesh


def _run_dryrun(n_devices, env_overrides, timeout=300, bench=False):
    env = dict(os.environ)
    # Start from an ambient environment that asks for the chip, not the
    # conftest's cpu-pinned one: the driver does not inherit our test env.
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "tpu"
    for k, v in env_overrides.items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    code = (f"import __graft_entry__ as g; "
            f"g.dryrun_multichip({n_devices}, bench={bench}); "
            f"print('DRYRUN_OK')")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)
    return proc


@pytest.mark.parametrize("n", [8])
def test_dryrun_multichip_under_tpu_env(n):
    """The exact round-2 failure mode: the ambient env asks for the TPU."""
    proc = _run_dryrun(n, {})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "DRYRUN_OK" in proc.stdout


@pytest.mark.slow
def test_dryrun_multichip_under_driver_cpu_env():
    """The documented driver recipe: host-platform device count + cpu."""
    proc = _run_dryrun(8, {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    })
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "DRYRUN_OK" in proc.stdout


@pytest.mark.slow
def test_dryrun_multichip_odd_device_count():
    proc = _run_dryrun(4, {})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "DRYRUN_OK" in proc.stdout


@pytest.mark.slow
def test_multichip_bench_metrics():
    """The MULTICHIP metrics sweep the driver records: real numbers at
    1/2/4/8 simulated devices plus the scaling-efficiency ratio."""
    import json

    proc = _run_dryrun(8, {}, timeout=480, bench=True)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = next(ln for ln in proc.stdout.splitlines()
                if ln.startswith("MULTICHIP_METRICS "))
    out = json.loads(line[len("MULTICHIP_METRICS "):])
    assert out["device_counts"] == [1, 2, 4, 8]
    for name in ("aggregate_range_scan_rows_per_sec",
                 "mesh_row_scan_rows_per_sec",
                 "tpch_q1_rows_per_sec", "tpch_q6_rows_per_sec"):
        by_dev = out["metrics"][name]["by_devices"]
        assert set(by_dev) == {"1", "2", "4", "8"}
        assert all(v > 0 for v in by_dev.values()), name
    # Throughput retention under 8-way partitioning (virtual devices
    # share one CPU, so this measures partition + collective overhead).
    assert out["scaling_efficiency"] >= 0.7, out["scaling_efficiency"]


def test_entry_compiles_in_process():
    """entry() must stay jittable (the driver compile-checks single-chip)."""
    import jax

    import __graft_entry__ as g

    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    jax.block_until_ready(out)
