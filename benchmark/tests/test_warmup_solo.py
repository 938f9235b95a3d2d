"""The solo warm-up pass: one client alone goes through the statements
before all clients warm up at once; a statement that fails (a cold
compile that outlasts the proxy's RPC budget) is asked again, and one
that keeps failing fails the run."""

import pytest

from benchmark.generators import sql_streams

PLAN = {"params": {"streams": 8, "statements": [
            {"name": "q1", "sql": "SELECT 1 FROM {table} WHERE a <= {x}",
             "draw": [{"name": "x", "range": [1, 9]}]},
            {"name": "q6", "sql": "SELECT 2 FROM {table} WHERE b < {y}",
             "draw": [{"name": "y", "choice": [24, 25]}]}]},
        "config": {"schema": {"sql_table": "t"}}, "addr": {"pg": ["h", 1]},
        "seed": 2147484099, "workers": 2, "worker": 1}


class FakeConn:
    """Fails the first ``fail`` executes of all connections together."""
    left = 0
    asked: list = []

    def __init__(self, *_a, **_kw):
        pass

    def execute(self, sql):
        FakeConn.asked.append(sql)
        if FakeConn.left > 0:
            FakeConn.left -= 1
            raise TimeoutError("rpc ts.scan timed out")

    def close(self):
        pass


@pytest.mark.parametrize("fail,errors,retried,asked", [
    (0, 0, 0, 2),     # warm: each statement once
    (2, 0, 2, 4),     # cold: the first statement asked three times
    (3, 1, 2, 3)])    # it never answers: the run fails, q6 is not asked
def test_solo_pass_asks_again_and_gives_up(monkeypatch, fail, errors,
                                           retried, asked):
    monkeypatch.setattr(sql_streams, "PgConnection", FakeConn)
    FakeConn.left, FakeConn.asked = fail, []
    gen = sql_streams.Generator(PLAN)
    assert gen.streams == [1, 3, 5, 7]
    gen.connect()
    got = gen.warmup_solo()
    assert (len(got["errors"]), len(got["retried"])) == (errors, retried)
    assert len(FakeConn.asked) == asked
    # an attempt that is asked again asks the same statement
    assert len(set(FakeConn.asked)) == (1 if errors else 2)
