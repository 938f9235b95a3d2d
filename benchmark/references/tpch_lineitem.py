"""TPC-H ``lineitem`` from a seed, and Q1/Q6 as plain numpy.

The yardstick for the ``tpch_lineitem_rf3`` deployment. It imports
nothing of the program. The generator follows the specification's clause
4.2.3 for all 16 columns of LINEITEM: 1 to 7 lines an order, sparse order
keys (8 of every 32), order dates uniform over 1992-01-01 .. 1998-08-02,
``l_shipdate`` = order date + 1..121, ``l_commitdate`` = order date +
30..90, ``l_receiptdate`` = ship date + 1..30, ``l_returnflag`` R or A
for a receipt on or before 1995-06-17 and N after, ``l_linestatus`` O for
a ship date after it and F before, ``l_partkey`` over SF1's 200,000 parts
with the spec's supplier and retail-price formulas. Money is scaled
integers (cents, whole percents) and dates are days since the epoch, as
the program's own ``yql/pgsql/tpch.py`` stores them. The queries are
direct transcriptions of the SQL over int64 arrays, with the spec's
substitution parameters as arguments. Every answer is an integer, or a
quotient of two integers, so the comparison is exact: limit 0.
"""

from __future__ import annotations

import numpy as np

STARTDATE = 8035      # 1992-01-01, days since the epoch
LAST_ORDERDATE = 10440  # 1998-08-02 = ENDDATE (1998-12-31) - 151 days
CURRENTDATE = 9298    # 1995-06-17
PARTS_SF1, SUPPLIERS_SF1 = 200_000, 10_000
INSTRUCTIONS = ("DELIVER IN PERSON", "COLLECT COD", "NONE",
                "TAKE BACK RETURN")
MODES = ("REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB")
# (a little of the spec's vocabulary, 4.2.2.13; comments are slices of a
# seeded pool of it, 10 to 43 characters, as dbgen cuts them from its pool)
WORDS = ("furiously sly carefully blithe quick fluffy slow quiet ruthless "
         "thin close dogged daring brave stealthy permanent enticing idle "
         "busy regular final ironic even bold silent packages requests "
         "accounts deposits foxes ideas theodolites pinto beans instructions "
         "dependencies excuses platelets asymptotes courts dolphins "
         "multipliers sauternes warthogs frets dinos attainments somas "
         "sleep wake are cajole haggle nag use boost affix detect integrate "
         "maintain nod was lose sublate solve thrash promise engage hinder "
         "print x-ray breach eat grow impress mold poach serve run dazzle "
         "snooze doze unwind kindle play hang believe doubt about above "
         "according to across after against along alongside of among "
         "around at atop before behind beneath beside besides between "
         "beyond by despite during except for from in place of inside "
         "instead of into near of on outside over past since through "
         "throughout to toward under until up upon without with within"
         ).split()
COLS = ("l_quantity", "l_extendedprice", "l_discount", "l_tax",
        "l_returnflag", "l_linestatus", "l_shipdate")
ALL_COLS = ("l_orderkey", "l_partkey", "l_suppkey", "l_linenumber") + COLS \
    + ("l_commitdate", "l_receiptdate", "l_shipinstruct", "l_shipmode",
       "l_comment")


class Lineitem:
    """Blocks of rows, as columns. The table is a function of the seed,
    the number of rows and the block size (the configuration's
    ``load.batch_ops``); an order never spans two blocks."""

    def __init__(self, num_rows: int, seed: int):
        self.left = num_rows
        self.rng = np.random.Generator(np.random.PCG64(seed))
        self.order = 0
        words = self.rng.choice(np.array(WORDS), 60_000)
        self.pool = " ".join(words.tolist())

    def block(self, n: int) -> dict[str, list]:
        n = min(n, self.left)
        rng = self.rng
        # Orders enough for n lines; the last order of a block is cut
        # where the block ends (and of the table, where the scale does).
        per = rng.integers(1, 8, n)
        orders = int(np.searchsorted(np.cumsum(per), n)) + 1
        per = per[:orders]
        per[-1] -= int(per.sum()) - n
        idx = self.order + np.arange(orders)
        self.order += orders
        okey = (idx // 8) * 32 + idx % 8 + 1
        odate = rng.integers(STARTDATE, LAST_ORDERDATE + 1, orders)
        line = np.concatenate([np.arange(1, k + 1) for k in per])
        okey, odate = np.repeat(okey, per), np.repeat(odate, per)
        part = rng.integers(1, PARTS_SF1 + 1, n)
        s = SUPPLIERS_SF1
        supp = (part + rng.integers(0, 4, n) * (s // 4 + (part - 1) // s)
                ) % s + 1
        qty = rng.integers(1, 51, n)
        retail = 90_000 + (part // 10) % 20_001 + 100 * (part % 1000)
        ship = odate + rng.integers(1, 122, n)
        commit = odate + rng.integers(30, 91, n)
        receipt = ship + rng.integers(1, 31, n)
        flag = np.where(receipt <= CURRENTDATE,
                        np.where(rng.integers(0, 2, n) == 0, "R", "A"), "N")
        status = np.where(ship > CURRENTDATE, "O", "F")
        at = rng.integers(0, len(self.pool) - 43, n).tolist()
        size = rng.integers(10, 44, n).tolist()
        pool = self.pool
        self.left -= n
        return {
            "l_orderkey": okey.tolist(), "l_partkey": part.tolist(),
            "l_suppkey": supp.tolist(), "l_linenumber": line.tolist(),
            "l_quantity": qty.tolist(),
            "l_extendedprice": (qty * retail).tolist(),
            "l_discount": rng.integers(0, 11, n).tolist(),
            "l_tax": rng.integers(0, 9, n).tolist(),
            "l_returnflag": flag.tolist(), "l_linestatus": status.tolist(),
            "l_shipdate": ship.tolist(), "l_commitdate": commit.tolist(),
            "l_receiptdate": receipt.tolist(),
            "l_shipinstruct": rng.choice(np.array(INSTRUCTIONS), n).tolist(),
            "l_shipmode": rng.choice(np.array(MODES), n).tolist(),
            "l_comment": [pool[a:a + k] for a, k in zip(at, size)],
        }


class Reference:
    """The table as arrays indexed by row number, filled while the rows
    are handed to the loader, so data is generated once."""

    def __init__(self, config: dict, seed: int):
        self.rows = int(config["scale"]["rows"])
        self.seed = seed
        self.table = config["schema"]["table"]
        self.ddl = config["schema"]["ddl"]
        self.batch = int(config["load"]["batch_ops"])
        self.filled = 0
        self.col = {c: np.zeros(self.rows, np.int64) for c in COLS}
        self._answers: dict = {}

    # -- data ---------------------------------------------------------------
    def batches(self, phase: str = "preload"):
        if phase != "preload":
            return
        gen = Lineitem(self.rows, self.seed)
        while self.filled < self.rows:
            cols = gen.block(self.batch)
            n = len(cols["l_orderkey"])
            span = slice(self.filled, self.filled + n)
            for c in COLS:
                v = cols[c]
                self.col[c][span] = [ord(x) for x in v] \
                    if isinstance(v[0], str) else v
            self.filled += n
            yield [dict(zip(ALL_COLS, r))
                   for r in zip(*(cols[c] for c in ALL_COLS))]

    def fill(self) -> None:
        """Generate without loading anything (tests, the control)."""
        for _ in self.batches():
            pass

    # -- queries ------------------------------------------------------------
    def q1(self, cutoff: int, acc=np.int64) -> list[list]:
        c = self.col
        m = c["l_shipdate"][:self.filled] <= cutoff
        flags, stats = c["l_returnflag"][:self.filled][m], \
            c["l_linestatus"][:self.filled][m]
        qty = c["l_quantity"][:self.filled][m].astype(acc)
        price = c["l_extendedprice"][:self.filled][m].astype(acc)
        disc_price = price * (100 - c["l_discount"][:self.filled][m]
                              ).astype(acc)
        charge = disc_price * (100 + c["l_tax"][:self.filled][m]).astype(acc)
        out = []
        for f in np.unique(flags):
            for s in np.unique(stats[flags == f]):
                g = (flags == f) & (stats == s)
                n = int(g.sum())
                sq, sp = _py(qty[g].sum(dtype=acc)), \
                    _py(price[g].sum(dtype=acc))
                out.append([chr(f), chr(s), sq, sp,
                            _py(disc_price[g].sum(dtype=acc)),
                            _py(charge[g].sum(dtype=acc)),
                            sq / n, sp / n, n])
        return sorted(out)

    def q6(self, lo: int, hi: int, dlo: int, dhi: int, qty: int,
           acc=np.int64) -> list[list]:
        c = {k: v[:self.filled] for k, v in self.col.items()}
        m = ((c["l_shipdate"] >= lo) & (c["l_shipdate"] < hi)
             & (c["l_discount"] >= dlo) & (c["l_discount"] <= dhi)
             & (c["l_quantity"] < qty))
        if not m.any():
            return [[None]]
        return [[_py((c["l_extendedprice"][m].astype(acc)
                      * c["l_discount"][m].astype(acc)).sum(dtype=acc))]]

    def answer(self, stmt: str, params: dict, acc=np.int64) -> list[list]:
        key = (stmt, tuple(sorted(params.items())), acc)
        if key not in self._answers:
            if stmt == "q1":
                self._answers[key] = self.q1(params["cutoff"], acc)
            elif stmt == "q6":
                self._answers[key] = self.q6(
                    params["lo"], params["hi"], params["dlo"],
                    params["dhi"], params["qty"], acc)
            else:
                raise KeyError(f"no reference for statement {stmt!r}")
        return self._answers[key]

    # -- the comparison -----------------------------------------------------
    def check(self, answers: list[dict]) -> dict:
        """``answers``: [{"stmt", "params", "rows"}] as the clients got
        them. Returns the numbers compared, each with its limit."""
        wrong = []
        for a in answers:
            want = self.answer(a["stmt"], a["params"])
            if _plain(a["rows"]) != want:
                wrong.append({"stmt": a["stmt"], "params": a["params"],
                              "got": a["rows"], "want": want})
        return {"compared": {"answers_checked": [len(answers), None],
                             "answers_wrong": [len(wrong), 0]},
                "wrong": len(wrong), "examples": wrong[:3]}

    def control_answers(self, answers: list[dict]) -> list[dict]:
        """The control: the reference in the program's place, summing in
        float32 — the approximate aggregate that would tempt a later PR.
        It must come out as not correct."""
        return [dict(a, rows=self.answer(a["stmt"], a["params"],
                                         acc=np.float32))
                for a in answers]


def control(config: dict, seed: int, traffic: dict, operations: int = 64):
    """A window's worth of the traffic's statements, answered (a) by the
    reference and (b) by the reference summing in float32."""
    import random

    from benchmark.generators.sql_streams import draw_params

    ref = Reference(config, seed)
    ref.fill()
    rng = random.Random(f"control/{seed}")
    stmts = traffic["params"]["statements"]
    asked = [{"stmt": s["name"], "params": draw_params(s["draw"], rng)}
             for i in range(operations) for s in [stmts[i % len(stmts)]]]
    sound = [dict(a, rows=ref.answer(a["stmt"], a["params"])) for a in asked]
    lowered = ref.control_answers(asked)
    by_stmt = {s["name"]: 0 for s in stmts}
    for a, low in zip(sound, lowered):
        by_stmt[a["stmt"]] += _plain(low["rows"]) != a["rows"]
    return {"operations": len(asked), "rows": ref.filled,
            "sound_wrong": ref.check(sound)["wrong"],
            "control_wrong": ref.check(lowered)["wrong"],
            "control_wrong_by_statement": by_stmt,
            "control": "sums in float32 for exact int64"}


def _py(v):
    return int(v) if isinstance(v, (np.integer, int)) else float(v)


def _plain(rows) -> list[list]:
    return [list(r) for r in rows]
