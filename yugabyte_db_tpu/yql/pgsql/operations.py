"""PgsqlReadOp: the pggate-shaped read operation.

Reference analog: PgsqlReadOperation::Execute
(src/yb/docdb/pgsql_operation.cc:345) with EvalAggregate/
PopulateAggregate (:473,487) — a read request carrying WHERE pushdown,
GROUP BY columns, and expression aggregates, executed against one
tablet's storage seam and combined above the scan. The TPU redesign
pushes the whole grouped/expression evaluation into one device dispatch
(ops.group_agg) when the engine can; this object is the API carrier.
"""

from __future__ import annotations

from dataclasses import dataclass, field

from yugabyte_db_tpu.storage.scan_spec import (AggSpec, ScanResult, ScanSpec,
                                                combine_grouped)


@dataclass
class PgsqlReadOp:
    """One pgsql-style read: build once, execute per tablet, combine."""

    spec: ScanSpec

    @staticmethod
    def aggregate(predicates=None, aggregates=None, group_by=None,
                  read_ht=None, lower=b"", upper=b"") -> "PgsqlReadOp":
        from yugabyte_db_tpu.storage.row_version import MAX_HT

        return PgsqlReadOp(ScanSpec(
            lower=lower, upper=upper,
            read_ht=read_ht if read_ht is not None else MAX_HT,
            predicates=list(predicates or []),
            aggregates=list(aggregates or []),
            group_by=list(group_by) if group_by else None))

    def execute(self, engine) -> ScanResult:
        """Run against one tablet's storage engine (the YQLStorageIf
        seam)."""
        return engine.scan(self.spec)

    def execute_partitioned(self, engines) -> ScanResult:
        """Run against many tablets and combine partial aggregates
        host-side (the above-the-scan combine of the reference's FDW /
        CQL executor)."""
        results = [e.scan(self.spec) for e in engines]
        return combine_grouped(self.spec, results)
