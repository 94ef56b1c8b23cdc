"""JAX MapReduce join engine: map-phase key generation, shuffle, reduce."""
from .executor import (JoinResult, build_pipeline, measure_loads,
                        predicted_comm, run_join, run_join_speculative)
from .keys import RouteSpec, build_route_specs, map_phase
from .local_join import (
    LocalJoinSpec,
    group_by_reducer,
    local_join_count_checksum,
    materialize_two_way,
)
from .naive import NaiveStats, naive_two_way
from .oracle import keyed_join_fingerprint, oracle_join
from .shuffle import run_distributed

__all__ = [
    "JoinResult",
    "LocalJoinSpec",
    "NaiveStats",
    "RouteSpec",
    "build_pipeline",
    "build_route_specs",
    "group_by_reducer",
    "keyed_join_fingerprint",
    "local_join_count_checksum",
    "map_phase",
    "materialize_two_way",
    "naive_two_way",
    "oracle_join",
    "measure_loads",
    "predicted_comm",
    "run_distributed",
    "run_join_speculative",
    "run_join",
]
