"""From a profiler trace to device busy time, per-module device time and
the breakdown of the last line.

Two stages, so that the arithmetic can be checked against a small
recorded trace (``testdata/recorded_trace.json``) without a profiler:

* :func:`load_xplane` reads an ``.xplane.pb`` with nothing but JAX and
  keeps, per device, the module events (one per executed XLA program,
  named ``jit_<function>``) and the op events;
* :func:`reduce_events` is pure arithmetic over those lists.

On the TPU a device is a plane ``/device:TPU:<n>`` with the lines
``XLA Modules`` and ``XLA Ops``. The CPU backend has no device plane: in
a rehearsal the executor threads (``tf_XLAPjRtCpuClient/*``) stand in for
the ops and the ``PjitFunction(<f>)`` dispatch spans for the modules, so
that the readers meet every name they will meet on the chip. Such a
reduction is labelled ``cpu`` in ``device`` and is not a device metric.
"""

from __future__ import annotations

import glob
import os
import re

_MODULE_ID = re.compile(r"\(\d+\)$")
_HOST_NOISE = ("ThreadpoolListener::", "SlinkyThreadPool::", "ThunkExecutor::")


def module_name(event_name: str) -> str:
    """``jit_replay_flush(8301297441)`` -> ``jit_replay_flush``."""
    return _MODULE_ID.sub("", event_name)


def op_name(event_name: str) -> str:
    """The TPU names an op by its whole HLO line, ``%while.3 = (s32[]...``:
    keep the name."""
    return event_name.split(" = ", 1)[0].lstrip("%")[:80]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_xplane(path: str) -> dict:
    """{"devices": {plane: {"modules": [[name, start_ns, dur_ns]...],
    "ops": [...]}}, "planes": {plane: [line names]}}."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    all_planes = [(plane.name, list(plane.lines)) for plane in data.planes]
    planes = {name: [ln.name for ln in lines] for name, lines in all_planes}
    devices: dict[str, dict] = {}
    on_chip = any(name.startswith("/device:") and lines
                  for name, lines in all_planes)
    for name, lines in all_planes:
        if on_chip and name.startswith("/device:"):
            dev = devices.setdefault(name, {"modules": [], "ops": []})
            for ln in lines:
                if ln.name == "XLA Modules":
                    dev["modules"] += _events(ln, rename=module_name)
                elif ln.name == "XLA Ops":
                    dev["ops"] += _events(ln, rename=op_name)
        elif not on_chip and name == "/host:CPU":
            dev = devices.setdefault("cpu-rehearsal",
                                     {"modules": [], "ops": []})
            for ln in lines:
                if ln.name.startswith("tf_XLAPjRtCpuClient"):
                    dev["ops"] += [e for e in _events(ln)
                                   if not e[0].startswith(_HOST_NOISE)]
                else:
                    dev["modules"] += [
                        ["jit_" + e[0][len("PjitFunction("):-1], e[1], e[2]]
                        for e in _events(ln)
                        if e[0].startswith("PjitFunction(")]
    return {"devices": devices, "planes": planes}


def _events(line, rename=None) -> list[list]:
    out = []
    for e in line.events:
        if e.duration_ns <= 0:
            continue
        name = rename(e.name) if rename else e.name
        out.append([name, float(e.start_ns), float(e.duration_ns)])
    return out


def union_ns(intervals: list[tuple[float, float]]) -> list[list[float]]:
    """Sorted, merged [start, end] intervals."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def clip_to_window(devices: dict, window_s: float) -> dict:
    """The devices that ran anything, their events cut to the traced
    window as the trace's own clock has it: ``window_s`` seconds from the
    first device event of any device. The trace's clock starts with the
    profiler's session, some 0.2 s before the go signal, and the profiler
    records until ``stop_trace`` has taken effect, milliseconds to tens of
    them after the host read ``window_s``; the chip is idle from the end
    of warm-up until the first request's program starts (about 15 ms
    after go), so that start is where the window is anchored. What a busy
    chip ran after the window's end is cut off, and so ``busy_s`` cannot
    pass ``window_s``."""
    devices = {n: d for n, d in devices.items() if d["ops"] or d["modules"]}
    if not devices:
        return {}
    t0 = min(e[1] for d in devices.values()
             for kind in ("ops", "modules") for e in d[kind])
    t1 = t0 + window_s * 1e9
    return {n: {kind: [[name, s, min(s + dur, t1) - s]
                       for name, s, dur in d[kind] if s < t1]
                for kind in ("ops", "modules")}
            for n, d in devices.items()}


def reduce_events(trace: dict, window_s: float, top: int = 10) -> dict:
    """busy_s (union of the intervals in which an op ran inside the
    traced window, see :func:`clip_to_window`, averaged over the devices
    that ran any), module_s/module_calls per module name (summed over
    devices), and the breakdown: the device ops that took most time, and
    the idle gaps between device programs, summed by the pair of modules
    they fell between."""
    devices = clip_to_window(trace["devices"], window_s)
    busy = []
    module_s: dict[str, float] = {}
    module_calls: dict[str, int] = {}
    op_s: dict[str, float] = {}
    gap_s: dict[str, float] = {}
    for dev in devices.values():
        events = dev["ops"] or dev["modules"]
        merged = union_ns([(s, s + d) for _n, s, d in events])
        busy.append(sum(e - s for s, e in merged) / 1e9)
        for name, _s, d in dev["modules"]:
            module_s[name] = module_s.get(name, 0.0) + d / 1e9
            module_calls[name] = module_calls.get(name, 0) + 1
        for name, _s, d in dev["ops"]:
            op_s[name] = op_s.get(name, 0.0) + d / 1e9
        mods = sorted(dev["modules"], key=lambda e: e[1])
        for prev, nxt in zip(mods, mods[1:]):
            gap = (nxt[1] - (prev[1] + prev[2])) / 1e9
            if gap > 0:
                key = f"{prev[0]} -> {nxt[0]}"
                gap_s[key] = gap_s.get(key, 0.0) + gap
        if mods:
            span = (mods[-1][1] + mods[-1][2] - mods[0][1]) / 1e9
            edge = window_s - span
            if edge > 0:
                gap_s["before the first / after the last program"] = \
                    gap_s.get("before the first / after the last program",
                              0.0) + edge

    def ranked(d: dict) -> list[list]:
        return [[k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    raw = [e for d in trace["devices"].values()
           for kind in ("ops", "modules") for e in d[kind]]
    return {
        "devices": len(devices),
        # (first device event to the last one's end, before the cut)
        "recorded_s": (max(s + d for _n, s, d in raw)
                       - min(s for _n, s, _d in raw)) / 1e9 if raw else 0.0,
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "module_s": module_s, "module_calls": module_calls,
        "breakdown": {"device_ops": ranked(op_s),
                      "idle_gaps": ranked(gap_s)},
    }
