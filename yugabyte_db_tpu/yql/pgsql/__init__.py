"""The YSQL layer: SQL frontend, pggate-shaped API, PG wire server.

Reference analog: the YSQL stack — PostgreSQL backend over pggate
(src/yb/yql/pggate/pggate.h:58) lowering to PgsqlReadOperation /
PgDocWriteOp (src/yb/docdb/pgsql_operation.cc:345, pg_doc_op.h:142).
Redesigned single-runtime: a SQL parser (parser.py) and executor
(executor.py) drive the same Cluster seam as the CQL frontend, with
grouped/expression aggregates pushed down to the storage engines (the
TPU engine runs them as one device dispatch per tablet); pggate.py is
the embedding API (PgApi/PgSession/PgStatement), wire.py the FE/BE v3
protocol server, and tpch.py the TPC-H Q1/Q6 workload benchmark/ measures.
"""

from yugabyte_db_tpu.yql.pgsql.executor import PgProcessor, PgResult
from yugabyte_db_tpu.yql.pgsql.operations import PgsqlReadOp
from yugabyte_db_tpu.yql.pgsql.parser import parse_script, parse_statement
from yugabyte_db_tpu.yql.pgsql.pggate import PgApi, PgSession, PgStatement
from yugabyte_db_tpu.yql.pgsql.tpch import (LINEITEM_COLUMNS,
                                            generate_lineitem, q1_result,
                                            q1_spec, q6_result, q6_spec)
from yugabyte_db_tpu.yql.pgsql.wire import PgServer

__all__ = [
    "LINEITEM_COLUMNS",
    "PgApi",
    "PgProcessor",
    "PgResult",
    "PgServer",
    "PgSession",
    "PgStatement",
    "PgsqlReadOp",
    "generate_lineitem",
    "parse_script",
    "parse_statement",
    "q1_result",
    "q1_spec",
    "q6_result",
    "q6_spec",
]
