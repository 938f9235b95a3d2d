"""Runtime compile witness: @compile_contract instrumentation + the
``--witness-check`` cross-validation against yb-lint's static ijit
facts.

Tier 1 counts real XLA compiles through a contracted factory, exercises
the witness dump/check exit codes, and runs one deterministic
fault-sweep round under ``compile_witness_out`` (must exit 0: runtime
compile behaviour never contradicts a static @compile_contract fact).
"""

import functools
import json
import tempfile
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from yugabyte_db_tpu.utils import jitting, metrics
from yugabyte_db_tpu.utils.jitting import compile_contract


@pytest.fixture(autouse=True)
def _witness_reset():
    jitting.witness().clear()
    yield
    jitting.disable_compile_witness()
    jitting.witness().clear()


def _obs(entry):
    for row in jitting.witness().observations():
        if row["entry"] == entry:
            return row
    return None


# -- decorator semantics -----------------------------------------------------

def test_declaration_is_registered():
    compile_contract("test_decl_entry", max_compiles=7)(lambda: None)
    assert jitting.declared_contracts()["test_decl_entry"] == 7


def test_non_literal_declaration_rejected():
    with pytest.raises(TypeError):
        compile_contract("", max_compiles=4)
    with pytest.raises(TypeError):
        compile_contract("x", max_compiles=0)
    with pytest.raises(TypeError):
        compile_contract(3, max_compiles=4)
    with pytest.raises(TypeError):
        compile_contract("x", max_compiles="4")


def test_factory_wraps_only_jitted_results():
    @compile_contract("test_passthrough", max_compiles=4)
    def factory(jitted):
        return jax.jit(lambda x: x) if jitted else (lambda x: x)

    assert isinstance(factory(True), jitting.ContractedJit)
    assert not isinstance(factory(False), jitting.ContractedJit)
    assert factory.__compile_contract__ == ("test_passthrough", 4)


def test_wrapper_delegates_attributes():
    @compile_contract("test_deleg", max_compiles=4)
    @jax.jit
    def double(x):
        return x + x

    assert isinstance(double, jitting.ContractedJit)
    assert callable(double.lower)          # jit API still reachable
    assert double._cache_size() == 0


# -- compile counting --------------------------------------------------------

def test_factory_counts_one_compile_per_signature():
    @functools.lru_cache(maxsize=None)
    @compile_contract("test_toy_factory", max_compiles=4)
    def toy(n):
        return jax.jit(lambda x: x * n)

    jitting.enable_compile_witness()
    before = metrics.jit_compiles("test_toy_factory")
    toy(2)(jnp.arange(3))      # compile 1
    toy(2)(jnp.arange(3))      # cache hit: no compile
    toy(2)(jnp.arange(5))      # new shape: compile 2
    toy(3)(jnp.arange(3))      # new factory signature: compile 3
    assert metrics.jit_compiles("test_toy_factory") - before == 3
    row = _obs("test_toy_factory")
    assert row["compiles"] == 3 and row["steady"] == 0
    assert row["budget"] == 4
    assert any("test_compile_witness" in s for s in row["sites"])


def test_threads_that_miss_at_once_share_one_wrapper_and_one_compile():
    """``functools.lru_cache`` calls the factory once a thread that
    misses before the first call has returned (two tablets' first scans
    of one new query); they get ONE jit object, so the program is traced
    once and the others wait for it."""
    made, traced = [], []

    @functools.lru_cache(maxsize=None)
    @compile_contract("test_toy_race", max_compiles=4)
    def toy(n):
        made.append(n)
        time.sleep(0.05)        # the window the other threads miss in

        def body(x):
            traced.append(n)
            return x * n
        return jax.jit(body)

    got = []
    gate = threading.Barrier(4)

    def one():
        gate.wait()
        fn = toy(7)
        got.append((fn, fn(jnp.arange(3)).tolist()))

    threads = [threading.Thread(target=one) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert made == [7] and traced == [7]
    assert len({id(fn) for fn, _out in got}) == 1
    assert [out for _fn, out in got] == [[0, 7, 14]] * 4


def test_direct_jit_counts_compiles():
    @compile_contract("test_toy_direct", max_compiles=2)
    @jax.jit
    def double(x):
        return x + x

    jitting.enable_compile_witness()
    before = metrics.jit_compiles("test_toy_direct")
    double(jnp.arange(4))
    double(jnp.arange(4))
    assert metrics.jit_compiles("test_toy_direct") - before == 1


def test_metric_counts_with_witness_disabled():
    @functools.lru_cache(maxsize=None)
    @compile_contract("test_toy_nowit", max_compiles=4)
    def toy(n):
        return jax.jit(lambda x: x + n)

    before = metrics.jit_compiles("test_toy_nowit")
    toy(5)(jnp.arange(2))
    assert metrics.jit_compiles("test_toy_nowit") - before == 1
    assert _obs("test_toy_nowit") is None  # witness off: no observation


def test_steady_state_compiles_tracked_separately():
    @functools.lru_cache(maxsize=None)
    @compile_contract("test_toy_steady", max_compiles=8)
    def toy(n):
        return jax.jit(lambda x: x - n)

    jitting.enable_compile_witness()
    toy(1)(jnp.arange(3))              # warmup compile
    jitting.mark_steady_state()
    toy(1)(jnp.arange(3))              # cache hit: nothing recorded
    toy(1)(jnp.arange(9))              # steady-state recompile
    row = _obs("test_toy_steady")
    assert row["compiles"] == 2 and row["steady"] == 1


# -- dump / load -------------------------------------------------------------

def test_dump_load_round_trip(tmp_path):
    @functools.lru_cache(maxsize=None)
    @compile_contract("test_toy_dump", max_compiles=4)
    def toy(n):
        return jax.jit(lambda x: x * x * n)

    jitting.enable_compile_witness()
    toy(2)(jnp.arange(3))
    path = str(tmp_path / "cwit.json")
    assert jitting.dump_compile_witness(path) == path
    data = jitting.load_compile_witness_dump(path)
    assert data["kind"] == "yb-compile-witness"
    rows = {o["entry"]: o for o in data["observations"]}
    assert rows["test_toy_dump"]["compiles"] == 1
    assert rows["test_toy_dump"]["budget"] == 4


def test_load_rejects_wrong_kind(tmp_path):
    p = tmp_path / "wrong.json"
    p.write_text(json.dumps({"kind": "yb-lock-witness", "observations": []}))
    with pytest.raises(ValueError):
        jitting.load_compile_witness_dump(str(p))


# -- witness-check exit codes ------------------------------------------------

def _witness_check(dump_path):
    from yugabyte_db_tpu.analysis.__main__ import main

    return main(["--witness-check", dump_path])


def _forged_dump(tmp_path, observations):
    p = tmp_path / "forged.json"
    p.write_text(json.dumps({"version": 1, "kind": "yb-compile-witness",
                             "observations": observations}))
    return str(p)


def test_witness_check_clean_dump_exits_zero(tmp_path, capsys):
    """Real compiles of a tree-contracted entry (ops.compact gc_mask)
    within budget: no contradiction."""
    from yugabyte_db_tpu.ops.compact import compiled_gc_mask

    jitting.enable_compile_witness()
    N = 12
    s = {"new_group": jnp.array([True] + [False] * (N - 1)),
         "tomb": jnp.zeros(N, jnp.bool_),
         "live": jnp.ones(N, jnp.bool_),
         "ht_hi": jnp.arange(N, 0, -1, dtype=jnp.int32),
         "ht_lo": jnp.zeros(N, jnp.int32),
         "exp_hi": jnp.full(N, 2**30, jnp.int32),
         "exp_lo": jnp.zeros(N, jnp.int32),
         "set_": jnp.ones((1, N), jnp.bool_)}
    planes = (jnp.int32(6), jnp.int32(0), jnp.int32(6), jnp.int32(0))
    compiled_gc_mask(1, N)(s, planes)
    assert _obs("gc_mask") is not None
    path = str(tmp_path / "cwit.json")
    jitting.dump_compile_witness(path)
    assert _witness_check(path) == 0
    assert "OK" in capsys.readouterr().out


def test_witness_check_budget_overrun_exits_two(tmp_path, capsys):
    path = _forged_dump(tmp_path, [
        {"entry": "seg_aggregate", "compiles": 999, "steady": 0,
         "budget": 128, "sites": ["forged.py:1"]}])
    assert _witness_check(path) == 2
    out = capsys.readouterr().out
    assert "seg_aggregate" in out and "max_compiles=128" in out


def test_witness_check_uncontracted_entry_exits_two(tmp_path, capsys):
    path = _forged_dump(tmp_path, [
        {"entry": "no_such_entry", "compiles": 1, "steady": 0,
         "budget": None, "sites": []}])
    assert _witness_check(path) == 2
    assert "no @compile_contract" in capsys.readouterr().out


def test_witness_check_steady_recompile_on_stable_exits_two(tmp_path, capsys):
    """seg_aggregate is statically proven stable (zero ijit findings),
    so a steady-state recompile contradicts the static pass."""
    path = _forged_dump(tmp_path, [
        {"entry": "seg_aggregate", "compiles": 2, "steady": 1,
         "budget": 128, "sites": []}])
    assert _witness_check(path) == 2
    assert "steady-state" in capsys.readouterr().out


def test_witness_check_rejects_non_dump(tmp_path):
    p = tmp_path / "not_a_dump.json"
    p.write_text("{}")
    assert _witness_check(str(p)) == 1


# -- the tier-1 integration round --------------------------------------------

def test_sweep_compile_witness_clean(tmp_path):
    """One deterministic fault-sweep round under the compile witness:
    every compile observed at runtime stays within its declared budget
    and no statically-stable entry recompiles (``--witness-check``
    exits 0)."""
    from yugabyte_db_tpu.integration.fault_sweep import FaultSweep

    path = str(tmp_path / "sweep_cwit.json")
    with tempfile.TemporaryDirectory() as root:
        summary = FaultSweep(root, seed=1234, ops_per_round=8,
                             schedule=("wal_sync", "hbm_eviction"),
                             compile_witness_out=path).run()
    assert summary["rounds"] == 2
    data = jitting.load_compile_witness_dump(path)
    assert data["observations"], "sweep compiled nothing?"
    assert _witness_check(path) == 0
