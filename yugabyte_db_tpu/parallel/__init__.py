"""Device-mesh parallelism: multi-tablet scans/aggregates over ICI.

The reference has NO intra-node scan parallelism — one thread walks one
RocksDB iterator per tablet (src/yb/docdb/doc_rowwise_iterator.cc:545), and
multi-tablet aggregates are merged client-side
(src/yb/docdb/pgsql_operation.cc:473, yql/cql/ql/exec/eval_aggr.cc). Here
the tablet axis is data-parallel ("dp") and the block axis within a tablet
is sequence-parallel ("sp"): tablets shard over the mesh's "t" axis, each
tablet's HBM-resident block sequence shards over "b", and the aggregate
combine that the reference does client-side becomes psum / two-plane
lexicographic pmax over ICI (BASELINE config 5).
"""

from yugabyte_db_tpu.parallel.sharded import (GroupedIneligible,
                                              ShardedTablets,
                                              sharded_aggregate,
                                              sharded_grouped_aggregate,
                                              sharded_row_page)
