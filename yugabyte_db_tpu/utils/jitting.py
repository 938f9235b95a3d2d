"""@compile_contract declarations + the runtime compile witness.

Reference analog: the reference tree keeps the storage hot path free of
per-request setup cost by pinning every prepared execution plan at the
``YQLStorageIf`` boundary; the JAX equivalent of "per-request setup" is
an unintended retrace — a jitted entry point recompiling because a
static argument, closure capture, or array shape varies per request.
This module supplies both halves of the discipline, mirroring
``utils/locking.py``'s @guarded_by + lock-witness pattern:

- :func:`compile_contract` is a decorator declaring "this jitted entry
  compiles at most N distinct programs over the life of the process".
  The declaration is a plain literal
  (``@compile_contract("seg_aggregate", max_compiles=32)``) so yb-lint's
  ``ijit/`` pass reads it straight off the AST and checks every call
  site statically for per-request static args, mutable closure captures,
  and data-derived shapes.

- The **compile witness** is the dynamic half: when enabled (the
  ``--compile_witness`` debug flag, or :func:`enable_compile_witness`
  in tests), every actual XLA trace/compile event of a contracted entry
  is counted (via the jitted callable's compiled-program cache size — a
  cache growth across a call IS a compile). A dump of those counts is
  fed to ``python -m yugabyte_db_tpu.analysis --witness-check <dump>``,
  which fails when any entry exceeds its declared budget or when an
  entry the static pass proved stable recompiled after
  :func:`mark_steady_state` — the static pass keeps the budgets honest,
  the witness keeps the static pass honest.

Every compile event also bumps ``yb_jit_compiles{entry=...}`` on the
process metric registry (witness on or off), so every daemon's
``/metrics`` scrape and every bench round can prove zero steady-state
recompiles, and records the seconds the compiling dispatch took as the
span ``engine.compile`` (``yb_jit_compile_seconds{entry=...}``, and a
line of the request's /rpcz sample). When the witness is disabled the
per-dispatch cost is two compiled-cache-size probes (C++ attribute
reads on the jit object) and one clock read.
"""

from __future__ import annotations

import functools
import json
import os
import threading
import time
import weakref
import zlib

# entry name -> declared max_compiles, in registration order. Filled at
# import time by @compile_contract decorations; read by the witness dump
# and by tests. The static pass reads the same budgets off the AST.
_CONTRACTS: dict[str, int] = {}
_CONTRACTS_LOCK = threading.Lock()


class CompileWitness:
    """Process-wide accumulator of per-entry compile counts. Everything
    is best-effort and exception-free: the witness observes the system,
    it must never perturb it."""

    _SITE_CAP = 8  # compile call sites kept per entry (enough to debug)

    def __init__(self):
        self._lock = threading.Lock()
        self.enabled = False
        self._steady = False
        # entry -> [compiles, steady_compiles, [sites...]]
        self._obs: dict[str, list] = {}

    def record(self, entry: str, n: int = 1) -> None:
        try:
            with self._lock:
                row = self._obs.get(entry)
                if row is None:
                    row = self._obs[entry] = [0, 0, []]
                row[0] += n
                if self._steady:
                    row[1] += n
                if len(row[2]) < self._SITE_CAP:
                    row[2].append(_caller_site())
        # The witness observes dispatches on the serve path; raising (or
        # even logging) from here would perturb the system under test.
        # yb-lint: disable=errors/swallowed-exception
        except Exception:  # noqa: BLE001 — witness must never throw
            pass

    def mark_steady_state(self) -> None:
        """Compiles recorded after this mark are *steady-state* — the
        warmup is over, every program the workload needs exists. A
        steady-state compile on an entry the static pass proved stable
        is a witness-check contradiction."""
        with self._lock:
            self._steady = True

    def observations(self) -> list[dict]:
        with self._lock, _CONTRACTS_LOCK:
            return [{"entry": e, "compiles": row[0], "steady": row[1],
                     "budget": _CONTRACTS.get(e), "sites": list(row[2])}
                    for e, row in sorted(self._obs.items())]

    def clear(self) -> None:
        with self._lock:
            self._obs.clear()
            self._steady = False

    def dump(self, path: str) -> str:
        payload = {"version": 1, "kind": "yb-compile-witness",
                   "observations": self.observations()}
        with open(path, "w", encoding="utf-8") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        return path


def _caller_site() -> str:
    """file:line of the dispatch that compiled (the frame below the
    contract wrapper); "?" when unavailable."""
    import sys

    try:
        f = sys._getframe(3)
        while f is not None and f.f_code.co_filename.endswith("jitting.py"):
            f = f.f_back
        if f is None:
            return "?"
        return f"{f.f_code.co_filename}:{f.f_lineno}"
    except Exception:  # noqa: BLE001 — witness must never throw
        return "?"


_WITNESS = CompileWitness()


def witness() -> CompileWitness:
    return _WITNESS


def enable_compile_witness() -> None:
    _WITNESS.enabled = True


def disable_compile_witness() -> None:
    _WITNESS.enabled = False


def compile_witness_enabled() -> bool:
    return _WITNESS.enabled


def mark_steady_state() -> None:
    _WITNESS.mark_steady_state()


def dump_compile_witness(path: str) -> str:
    return _WITNESS.dump(path)


def load_compile_witness_dump(path: str) -> dict:
    with open(path, "r", encoding="utf-8") as f:
        data = json.load(f)
    if data.get("kind") != "yb-compile-witness":
        raise ValueError(f"{path}: not a compile-witness dump")
    return data


def declared_contracts() -> dict[str, int]:
    """entry -> max_compiles for every contract registered at runtime."""
    with _CONTRACTS_LOCK:
        return dict(_CONTRACTS)


# -- the persistent compile cache ---------------------------------------------

# One fixed directory inside the checkout (git-ignored), never a temp
# name: the path is part of the cache's key, so a directory that moves
# never hits.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_compile_cache")


def enable_compile_cache() -> str | None:
    """Keep compiled device programs across processes; returns the
    directory. Every process that compiles device programs (daemon,
    chip_smoke, bench, sweep CLIs) calls this before its first compile.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already uses that
    directory and nothing here names another; otherwise the cache lives
    at :data:`COMPILE_CACHE_DIR`, and the variable is exported so child
    processes share it. Short compiles persist too (JAX's default keeps
    only programs that took over a second to compile).

    A process pinned to the CPU backend (``JAX_PLATFORMS=cpu``: the
    tests, a rehearsal, yb_ctl's cpu daemons) gets no cache and None:
    the cache is for the chip, test runs stay independent of each other,
    and XLA:CPU logs a machine-feature warning on every cache hit."""
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        return None
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.environ["JAX_COMPILATION_CACHE_DIR"] = COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


# -- names for the device programs ---------------------------------------------

def tag(**parts) -> str:
    """A short descriptor of what a compiled program computes, the same
    in every process and run: per part its first letter and its length
    (a tuple) or value (an int or bool), then six hex digits of a CRC
    over the parts' ``repr`` (dataclasses, tuples, strings and numbers
    only: nothing whose repr holds an address). Pass what the query
    decides (group columns, aggregates, predicates, flat or not), not
    shapes (B, R, K): one query keeps one name whatever the run's size,
    and an entry has at most ``max_compiles`` distinct tags."""
    head = "".join(
        f"{k[0]}{len(v) if isinstance(v, (tuple, list)) else int(v)}"
        for k, v in parts.items())
    crc = zlib.crc32(repr(sorted(parts.items())).encode()) & 0xFFFFFF
    return f"{head}_{crc:06x}"


def jit(fn, entry: str, tag: str | None = None, **jit_kwargs):
    """``jax.jit(fn)`` under the name ``<entry>[_<tag>]``: the XLA module
    is ``jit_<entry>_<tag>`` in a device trace, where a bare
    ``functools.partial`` would be ``jit__unknown``. Every
    ``@compile_contract`` factory builds its jit here. The name is all
    that changes: ``fn`` is traced as it is."""
    import jax

    named = functools.partial(fn)
    named.__name__ = named.__qualname__ = \
        f"{entry}_{tag}" if tag else entry
    return jax.jit(named, **jit_kwargs)


# -- the declaration decorator ------------------------------------------------

def _is_jitted(obj) -> bool:
    """A jax.jit product: exposes the compiled-program cache probe."""
    return callable(obj) and hasattr(obj, "_cache_size")


def _note_compiles(entry: str, n: int, wall_ns: int, seconds: float) -> None:
    from yugabyte_db_tpu.utils import metrics, trace

    metrics.count_jit_compile(entry, n)
    # The dispatch that compiled: its whole call, trace + compile (or
    # the load from the persistent cache) + enqueue.
    trace.record_span("engine.compile", wall_ns, int(seconds * 1e6),
                      metrics.jit_compile_histogram(entry), seconds=True,
                      entry=entry)
    if _WITNESS.enabled:
        _WITNESS.record(entry, n)


class ContractedJit:
    """Wraps a jitted callable; a growth of its compiled-program cache
    across a dispatch is a trace/compile event for the contract's entry.
    Transparent otherwise — attribute access delegates to the jit
    object, so ``.lower``/``.clear_cache`` etc. keep working."""

    __slots__ = ("_fn", "_entry", "__weakref__")

    def __init__(self, fn, entry: str):
        self._fn = fn
        self._entry = entry

    def __call__(self, *args, **kwargs):
        fn = self._fn
        try:
            before = fn._cache_size()
        except Exception:  # noqa: BLE001 — probe is best-effort
            before = None
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        if before is not None:
            try:
                delta = fn._cache_size() - before
            except Exception:  # noqa: BLE001 — probe is best-effort
                delta = 0
            if delta > 0:
                dt = time.perf_counter() - t0
                _note_compiles(self._entry, delta,
                               time.time_ns() - int(dt * 1e9), dt)
        return out

    def __getattr__(self, name):
        return getattr(self._fn, name)


def compile_contract(entry: str, max_compiles: int):
    """Declare a jitted entry point's compile budget.

    Pure-literal usage only (string + int constants), so the static pass
    can read the declaration off the AST. Two shapes:

    - a **factory** returning ``jitting.jit(fn, entry, tag)`` (which
      names the program after the entry) — decorate *under* the
      ``lru_cache`` so the signature cache keeps one wrapper per
      signature::

          @functools.lru_cache(maxsize=128)
          @compile_contract("seg_aggregate", max_compiles=32)
          def compiled_seg_aggregate(sig): ...

    - a **directly jitted** function — decorate above the jit::

          @compile_contract("replay_flush", max_compiles=8)
          @functools.partial(jax.jit, static_argnames=("R",))
          def replay_flush(...): ...

    Either way the callable the caller ends up holding counts actual
    XLA compile events against ``yb_jit_compiles{entry=...}`` and, when
    enabled, the compile witness. ``max_compiles`` bounds the *distinct
    compiled programs* over the process lifetime (one per static
    signature / shape bucket), not dispatches.
    """
    if not isinstance(entry, str) or not entry \
            or not isinstance(max_compiles, int) or max_compiles < 1:
        raise TypeError("compile_contract(entry, max_compiles) takes a "
                        "string literal and a positive int literal")
    with _CONTRACTS_LOCK:
        _CONTRACTS[entry] = max_compiles

    def deco(obj):
        if _is_jitted(obj):
            wrapped = ContractedJit(obj, entry)
            return wrapped

        # One wrapper a signature while anything holds it: threads that
        # miss the ``lru_cache`` above at the same moment (two tablets'
        # first scans of a new query, a statement asked again) each call
        # the factory, and each jit object of their own would trace and
        # compile the same program side by side under the GIL. One jit
        # object compiles once: jax makes its other callers wait.
        made = weakref.WeakValueDictionary()
        lock = threading.Lock()

        @functools.wraps(obj)
        def factory(*args, **kwargs):
            key = (args, tuple(sorted(kwargs.items())))
            with lock:
                out = made.get(key)
                if out is None:
                    out = obj(*args, **kwargs)
                    if _is_jitted(out):
                        out = made[key] = ContractedJit(out, entry)
            return out

        factory.__compile_contract__ = (entry, max_compiles)
        return factory

    return deco
