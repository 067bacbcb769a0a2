"""cylon_tpu — a TPU-native distributed dataframe engine.

A from-scratch rebuild of the capabilities of Cylon (reference mounted at
/root/reference): Arrow-style columnar tables resident in TPU HBM,
relational kernels (join, union, intersect, subtract, groupby, sort) as
vectorized JAX/Pallas programs, and the distributed shuffle mapped onto XLA
collectives (`all_to_all`, `psum`) over ICI/DCN under `shard_map` SPMD —
no MPI, no per-rank processes, one controller driving a device mesh.
"""

from .config import (CommConfig, CommType, CSVReadOptions, CSVWriteOptions,
                     LocalConfig, MPIConfig, MultiHostConfig, ParquetOptions,
                     TPUConfig)
from .context import CylonContext
from . import telemetry
from .data.column import Column
from .data.row import Row
from .data.table import Table, concat_tables, join, set_op
from .dtypes import DataType, Layout, Type
from .io.csv import read_csv, read_csv_per_rank, write_csv
from .io.parquet import read_parquet, read_parquet_per_rank, write_parquet
from .ops.groupby import AggregationOp
from .ops.join import JoinAlgorithm, JoinConfig, JoinType
from . import native
from .parallel.dist_ops import (distributed_groupby, distributed_join,
                                distributed_set_op,
                                distributed_sort, hash_partition,
                                repartition, shuffle)
from .parallel.shard import distribute_by_key
from . import plan
from .plan import LazyTable, col
from . import resilience
from . import service
from .service import QueryService, QueryTicket
from .status import (Code, CylonDataError, CylonError, CylonPlanError,
                     CylonResourceExhausted, CylonTimeoutError,
                     CylonTransientError, Status)

__version__ = "0.1.0"

__all__ = [
    "AggregationOp", "Code", "Column", "CommConfig", "CommType",
    "CSVReadOptions", "CSVWriteOptions", "CylonContext",
    "CylonDataError", "CylonError", "CylonPlanError",
    "CylonResourceExhausted", "CylonTimeoutError",
    "CylonTransientError",
    "DataType", "JoinAlgorithm", "JoinConfig", "JoinType", "Layout",
    "LazyTable", "LocalConfig", "MPIConfig", "MultiHostConfig",
    "ParquetOptions", "QueryService", "QueryTicket", "Row", "col",
    "plan", "resilience", "service",
    "Status", "TPUConfig", "Table", "Type", "concat_tables",
    "distribute_by_key", "distributed_groupby", "distributed_join",
    "distributed_set_op",
    "distributed_sort", "hash_partition", "join", "native", "read_csv",
    "read_csv_per_rank",
    "read_parquet", "read_parquet_per_rank", "repartition", "set_op",
    "shuffle", "telemetry",
    "write_csv", "write_parquet",
]
