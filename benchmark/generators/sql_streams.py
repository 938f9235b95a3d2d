"""Closed-loop SQL streams over the PG wire: each stream is one connection
that sends the traffic file's statements in turn, each with parameters
drawn from the seed, and waits for every reply (TPC-H's query streams,
clause 5.3: callers that wait).

Traffic parameters: ``streams`` (over all processes), ``timeout_s``,
``warmup_rounds``, and ``statements``: ``name``, ``sql`` with ``{param}``
and ``{table}`` places, ``class`` (the class its latency is reported under;
its name if absent), and
``draw``: a list of {"name", then one of "range": [lo, hi] inclusive,
"choice": [...], "from": other, "add": k, or "from": other, "map":
{value of other: value}}.
"""

from __future__ import annotations

import random
import threading
import time

from benchmark.clients.minipg import PgConnection

SOLO_ATTEMPTS = 3


def draw_params(spec: list[dict], rng: random.Random) -> dict:
    out: dict = {}
    for d in spec:
        if "range" in d:
            out[d["name"]] = rng.randint(d["range"][0], d["range"][1])
        elif "choice" in d:
            out[d["name"]] = rng.choice(d["choice"])
        elif "map" in d:
            out[d["name"]] = d["map"][str(out[d["from"]])]
        else:
            out[d["name"]] = out[d["from"]] + d["add"]
    return out


class Generator:
    def __init__(self, plan: dict):
        p = plan["params"]
        self.statements = p["statements"]
        self.timeout_s = float(p.get("timeout_s", 60))
        self.warmup_rounds = int(p.get("warmup_rounds", 1))
        self.table = plan["config"]["schema"]["sql_table"]
        self.addr = tuple(plan["addr"][p.get("wire", "pg")])
        # This process's share of the streams, by stream number.
        self.streams = [s for s in range(int(p["streams"]))
                        if s % plan["workers"] == plan["worker"]]
        self.seed = plan["seed"]
        self.conns: dict[int, PgConnection] = {}

    def connect(self) -> None:
        for s in self.streams:
            self.conns[s] = PgConnection(*self.addr, timeout=self.timeout_s)

    def close(self) -> None:
        for c in self.conns.values():
            c.close()

    def _sql(self, stmt: dict, params: dict) -> str:
        return stmt["sql"].format(table=self.table, **params)

    def _threads(self, target) -> None:
        threads = [threading.Thread(target=target, args=(s,), daemon=True)
                   for s in self.streams]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    def warmup_solo(self) -> dict:
        """One stream alone, once through the statements, before all the
        streams warm up at once. In a checkout whose compile cache is
        empty the first query of each kind compiles its device programs;
        eight streams that start cold at once each wait for compiles of
        the same two programs, sixteen at a time, and outlast the proxy's
        RPC budget (10 s, the daemon's default, and the deployment's).
        Alone, the first query still takes most of that budget (compile
        and first upload), so a statement that fails here is asked again,
        ``SOLO_ATTEMPTS`` times in all: the server finishes the compile
        whether or not the proxy waited for it. What was asked again is
        reported (``retried``) and shows in ``setup_s``."""
        errors: list[str] = []
        retried: list[str] = []
        t0 = time.perf_counter()
        s = self.streams[0]
        rng = random.Random(f"warm-solo/{self.seed}/{s}")
        for stmt in self.statements:
            sql = self._sql(stmt, draw_params(stmt["draw"], rng))
            for attempt in range(1, SOLO_ATTEMPTS + 1):
                try:
                    self.conns[s].execute(sql)
                    break
                except Exception as e:  # noqa: BLE001 — reported
                    note = (f"stream {s} alone, {stmt['name']}, attempt "
                            f"{attempt}: {e!r}")
                    if attempt == SOLO_ATTEMPTS:
                        errors.append(note)
                        break
                    retried.append(note)
                    try:
                        self.conns[s].close()
                    except OSError:
                        pass
                    self.conns[s] = PgConnection(*self.addr,
                                                 timeout=self.timeout_s)
            if errors:
                break
        return {"seconds": time.perf_counter() - t0, "errors": errors,
                "retried": retried}

    def warmup(self) -> dict:
        """The cell's own statements, all streams at once, with other
        parameters than the window will draw."""
        errors: list[str] = []

        def one(s: int) -> None:
            rng = random.Random(f"warm/{self.seed}/{s}")
            try:
                for _ in range(self.warmup_rounds):
                    for stmt in self.statements:
                        self.conns[s].execute(
                            self._sql(stmt, draw_params(stmt["draw"], rng)))
            except Exception as e:  # noqa: BLE001 — reported, run fails
                errors.append(f"stream {s}: {e!r}")

        t0 = time.perf_counter()
        self._threads(one)
        return {"seconds": time.perf_counter() - t0, "errors": errors}

    def run(self, seconds: float) -> dict:
        results: dict[int, dict] = {}

        def one(s: int) -> None:
            rng = random.Random(f"run/{self.seed}/{s}")
            conn = self.conns[s]
            ok, failed = 0, 0
            classes = {st.get("class", st["name"]) for st in self.statements}
            lat: dict[str, list] = {k: [] for k in classes}
            done: dict[str, list] = {k: [] for k in classes}
            answers, errors = [], []
            start = time.perf_counter()
            # Streams start one statement apart, so that concurrent
            # streams do not all ask the same statement at once.
            i = s
            while time.perf_counter() - start < seconds:
                stmt = self.statements[i % len(self.statements)]
                i += 1
                params = draw_params(stmt["draw"], rng)
                t0 = time.perf_counter()
                try:
                    rows = conn.execute(self._sql(stmt, params)).rows
                except Exception as e:  # noqa: BLE001 — counted as failed
                    failed += 1
                    errors.append(f"stream {s} {stmt['name']}: {e!r}")
                    try:
                        conn.close()
                        conn = self.conns[s] = PgConnection(
                            *self.addr, timeout=self.timeout_s)
                    except OSError as e2:
                        errors.append(f"stream {s} reconnect: {e2!r}")
                        break
                    continue
                t1 = time.perf_counter()
                ok += 1
                lat[stmt.get("class", stmt["name"])].append((t1 - t0) * 1e3)
                done[stmt.get("class", stmt["name"])].append(t1 - start)
                answers.append({"stmt": stmt["name"], "params": params,
                                "rows": [list(r) for r in rows]})
            # (the stream's whole time: a failed or timed-out operation
            # at its end counts in it)
            results[s] = {"ok": ok, "elapsed": time.perf_counter() - start,
                          "failed": failed, "lat": lat, "done": done,
                          "answers": answers, "errors": errors}

        self._threads(one)
        rs = [results[s] for s in self.streams]
        return {
            "streams": [[r["ok"], r["elapsed"]] for r in rs],
            "latency_ms": {k: [x for r in rs for x in r["lat"][k]]
                           for k in rs[0]["lat"]} if rs else {},
            "done_s": {k: [x for r in rs for x in r["done"][k]]
                       for k in rs[0]["done"]} if rs else {},
            "attempted": sum(r["ok"] + r["failed"] for r in rs),
            "failed": sum(r["failed"] for r in rs),
            "answers": [a for r in rs for a in r["answers"]],
            "errors": [e for r in rs for e in r["errors"]][:5],
        }

    def after(self) -> dict:
        return {"compared": {}, "wrong": 0}
