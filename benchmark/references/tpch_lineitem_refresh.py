"""TPC-H ``lineitem`` after the refresh functions, and Q1/Q6 over it.

The yardstick for the ``tpch_lineitem_refresh_rf3`` deployment, on top of
``tpch_lineitem`` (the base table, the queries, the comparison). It
imports nothing of the program. A refresh pair is RF1 (the lineitems of
``scale.refresh_orders`` new orders, inserted) and RF2 (every lineitem of
as many old orders, deleted); ``scale.refresh_pairs`` pairs are run, all
of RF1 before all of RF2 (their key sets are disjoint, so the end state
is that of pairs in turn):

* RF1's orders take the order keys the population leaves free (it uses
  the first 8 of every 32: ``(i // 8) * 32 + i % 8 + 1``; the update sets
  take the next 8), 1 to 7 lines each with the columns' distributions of
  clause 4.2.3 (``tpch_lineitem.Lineitem.block``'s), from a stream of
  random numbers of their own so that the base table is the base
  deployment's, row for row. ``batches("burst")`` hands them to the
  loader.
* RF2 deletes the ``refresh_pairs x refresh_orders`` lowest order keys of
  the base table, every line of each. ``delete_keys`` gives the load
  generator each key with its line count, from the seed alone and without
  the table.

Q1 and Q6 are the base reference's over base less deleted plus inserted:
every answer an integer or a quotient of two, limit 0.
"""

from __future__ import annotations

import numpy as np

from benchmark.references import tpch_lineitem as base
from benchmark.references.tpch_lineitem import ALL_COLS, COLS, _plain


def _refresh_orders(config: dict) -> int:
    scale = config["scale"]
    return int(scale["refresh_pairs"]) * int(scale["refresh_orders"])


def delete_keys(config: dict, seed: int) -> list[tuple[int, int]]:
    """RF2's statements: (order key, its number of lines) for the lowest
    order keys of the base table. The population hands out its keys in
    rising order, so they are its first orders: made again from the seed,
    as many blocks as hold them."""
    want = _refresh_orders(config)
    gen = base.Lineitem(int(config["scale"]["rows"]), seed)
    out: list[tuple[int, int]] = []
    while len(out) < want and gen.left:
        okey = np.asarray(gen.block(int(config["load"]["batch_ops"]))[
            "l_orderkey"])
        keys, lines = np.unique(okey, return_counts=True)
        out += list(zip(keys.tolist(), lines.tolist()))
    if len(out) < want:
        raise ValueError(f"the table holds {len(out)} orders, the refresh "
                         f"deletes {want}")
    return out[:want]


def insert_block(config: dict, seed: int) -> dict[str, list]:
    """RF1's lineitems as columns: ``Lineitem.block``'s rows for the
    first orders of a population of their own, cut where the last new
    order ends, under the update sets' order keys."""
    orders = _refresh_orders(config)
    # (an order has at most 7 lines: 7 x orders lines hold that many)
    cols = base.Lineitem(7 * orders, np.random.SeedSequence(
        [seed, 0x5246_31]).generate_state(1)[0]).block(7 * orders)
    okey = np.asarray(cols["l_orderkey"])
    keep = int(np.searchsorted(okey, np.unique(okey)[orders - 1],
                               side="right"))
    cols = {c: v[:keep] for c, v in cols.items()}
    cols["l_orderkey"] = (okey[:keep] + 8).tolist()
    return cols


class Reference(base.Reference):
    """The base table's arrays, the deleted rows cut out and the
    inserted appended once RF1's rows have been handed to the loader."""

    def __init__(self, config: dict, seed: int, rf2: bool = True):
        super().__init__(config, seed)
        self.config = config
        self.rf2 = rf2      # False: the control's table, RF2 left out
        self.inserted = 0
        self.deleted = 0

    def batches(self, phase: str = "preload"):
        if phase == "preload":
            okeys = []
            gen = super().batches()
            for rows in gen:
                okeys.append([r["l_orderkey"] for r in rows])
                yield rows
            self._okey = np.concatenate(okeys)
            return
        if phase != "burst" or self.inserted:
            return
        cols = insert_block(self.config, self.seed)
        n = len(cols["l_orderkey"])
        gone = np.isin(self._okey[:self.filled], np.array(
            [k for k, _lines in delete_keys(self.config, self.seed)]
            if self.rf2 else [], np.int64))
        self.deleted = int(gone.sum())
        for c in COLS:
            v = cols[c]
            new = np.array([ord(x) for x in v] if isinstance(v[0], str)
                           else v, np.int64)
            self.col[c] = np.concatenate(
                [self.col[c][:self.filled][~gone], new])
        self.inserted = n
        self.filled = self.filled - self.deleted + n
        self._answers.clear()
        for at in range(0, n, self.batch):
            yield [dict(zip(ALL_COLS, r)) for r in zip(
                *(cols[c][at:at + self.batch] for c in ALL_COLS))]

    def fill(self) -> None:
        for phase in ("preload", "burst"):
            for _ in self.batches(phase):
                pass


def control(config: dict, seed: int, traffic: dict, operations: int = 64):
    """A window's worth of the traffic's statements, answered (a) by the
    reference, (b) by the reference summing in float32 and (c) by the
    reference WITHOUT RF2 (the deleted lines still counted: a refresh
    half applied). (a) must pass; (b) and (c) must each come out not
    correct."""
    import random

    from benchmark.generators.sql_streams import draw_params

    ref = Reference(config, seed)
    ref.fill()
    stale = Reference(config, seed, rf2=False)
    stale.fill()
    rng = random.Random(f"control/{seed}")
    stmts = traffic["params"]["statements"]
    asked = [{"stmt": s["name"], "params": draw_params(s["draw"], rng)}
             for i in range(operations) for s in [stmts[i % len(stmts)]]]
    sound = [dict(a, rows=ref.answer(a["stmt"], a["params"])) for a in asked]
    lowered = ref.control_answers(asked)
    no_rf2 = [dict(a, rows=stale.answer(a["stmt"], a["params"]))
              for a in asked]
    by_stmt = {s["name"]: 0 for s in stmts}
    for a, low in zip(sound, lowered):
        by_stmt[a["stmt"]] += _plain(low["rows"]) != a["rows"]
    wrong_f32 = ref.check(lowered)["wrong"]
    wrong_rf2 = ref.check(no_rf2)["wrong"]
    return {"operations": len(asked), "rows": ref.filled,
            "inserted": ref.inserted, "deleted": ref.deleted,
            "sound_wrong": ref.check(sound)["wrong"],
            # (both controls have to fail: the lesser count is reported)
            "control_wrong": min(wrong_f32, wrong_rf2),
            "control_wrong_float32": wrong_f32,
            "control_wrong_without_rf2": wrong_rf2,
            "control_wrong_by_statement": by_stmt,
            "control": "sums in float32 for exact int64; RF2 left out"}
