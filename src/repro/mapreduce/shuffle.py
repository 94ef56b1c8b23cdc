"""Distributed shuffle: shard_map + all_to_all over a mesh axis.

This is the MapReduce shuffle mapped onto the TPU fabric (DESIGN.md §2):
each device maps its input shard, packs per-destination-device send buffers
(static capacity — the paper's reducer bound q gives the budget), exchanges
them with a single all_to_all, then bins received tuples into its local
block of reducers and joins.  Reducer ids are block-partitioned over the
axis: device d owns global reducers [d*g, (d+1)*g).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.planner import SharesSkewPlan
from repro.core.schema import JoinQuery
from repro.obs import NULL_OBS, Observability

from .executor import JoinResult, _bin_cap, predicted_comm
from .keys import map_phase
from .local_join import LocalJoinSpec, group_by_reducer, local_join_per_reducer


def _pad_shard(arr: np.ndarray, d: int) -> tuple[np.ndarray, np.ndarray]:
    """Pad leading dim to a multiple of d; returns (padded, valid_mask)."""
    n = arr.shape[0]
    n_pad = int(math.ceil(max(n, 1) / d) * d)
    out = np.zeros((n_pad,) + arr.shape[1:], dtype=arr.dtype)
    out[:n] = arr
    mask = np.zeros(n_pad, dtype=bool)
    mask[:n] = True
    return out, mask


def route_caps(plan: SharesSkewPlan, d: int, route_cap_factor: float) -> dict[str, int]:
    """Per relation, the static capacity of each device's send buffer to
    each other device: the plan's exact emissions spread evenly over the
    ``d * d`` pairs of devices, times ``route_cap_factor``, plus slack."""
    pred = predicted_comm(plan)
    return {
        name: max(32, int(math.ceil(pred[name] / (d * d) * route_cap_factor)) + 16)
        for name in pred
    }


def distributed_program(
    query: JoinQuery,
    plan: SharesSkewPlan,
    mesh: Mesh,
    axis_name: str = "shuffle",
    cap_factor: float = 3.0,
    route_cap_factor: float = 3.0,
):
    """The jitted map → all_to_all → reduce program of ``run_distributed``.

    It takes ``(rows, masks)``, one tuple each of the relations' rows
    ``[n_pad, arity]`` int32 and validity ``[n_pad]`` bool, sharded over
    ``axis_name``, and returns ``(counts [k_pad], checksum, comm [n_rel],
    loads [k_pad], overflow)`` — counts per reducer, so the caller sums a
    total past 2^31 exactly.  Static over the plan and the mesh only, so
    it can be lowered for a described topology from shapes alone.
    """
    d = mesh.shape[axis_name]
    k = plan.total_reducers
    g = int(math.ceil(k / d))  # reducers per device
    cap = _bin_cap(plan, cap_factor)
    spec = LocalJoinSpec.from_query(query)

    caps = route_caps(plan, d, route_cap_factor)

    def stage(rows_list, mask_list):
        my_dev = jax.lax.axis_index(axis_name)
        bins, valids = {}, {}
        loads_local = jnp.zeros(g, dtype=jnp.int32)
        comm = []
        overflow = jnp.int32(0)
        for rel, rows, rowmask in zip(query.relations, rows_list, mask_list):
            rcap = caps[rel.name]
            dest = map_phase(plan, rel, rows)  # [n_loc, W]
            dest = jnp.where(rowmask[:, None], dest, jnp.int32(-1))
            n, w = dest.shape
            flat_dest = dest.reshape(-1)
            flat_rows = jnp.broadcast_to(
                rows[:, None, :], (n, w, rows.shape[1])
            ).reshape(-1, rows.shape[1])
            comm.append(jnp.sum(flat_dest >= 0))
            # ---- pack per-destination-device send buffers ----
            dev_ids = jnp.where(flat_dest >= 0, flat_dest // g, jnp.int32(-1))
            payload = jnp.concatenate([flat_rows, flat_dest[:, None]], axis=1)
            send, send_ok, _, ov1 = group_by_reducer(dev_ids, payload, d, rcap)
            # ---- the shuffle ----
            recv = jax.lax.all_to_all(send, axis_name, split_axis=0, concat_axis=0)
            recv_ok = jax.lax.all_to_all(
                send_ok.astype(jnp.int32), axis_name, split_axis=0, concat_axis=0
            ).astype(bool)
            rr = recv.reshape(-1, payload.shape[1])
            ok = recv_ok.reshape(-1)
            gdest = rr[:, -1]
            local = jnp.where(ok, gdest - my_dev * g, jnp.int32(-1))
            b, v, loads, ov2 = group_by_reducer(local, rr[:, :-1], g, cap)
            bins[rel.name], valids[rel.name] = b, v
            loads_local = loads_local + loads
            overflow = overflow + ov1 + ov2
        counts, checksum = local_join_per_reducer(spec, bins, valids)
        checksum = jax.lax.psum(jnp.sum(checksum), axis_name)
        comm = [jax.lax.psum(c, axis_name) for c in comm]
        overflow = jax.lax.psum(overflow, axis_name)
        return counts, checksum, jnp.stack(comm), loads_local, overflow

    n_rel = len(query.relations)
    in_row_specs = [P(axis_name) for _ in range(n_rel)]
    in_mask_specs = [P(axis_name) for _ in range(n_rel)]
    fn = shard_map(
        stage,
        mesh=mesh,
        in_specs=(tuple(in_row_specs), tuple(in_mask_specs)),
        out_specs=(P(axis_name), P(), P(), P(axis_name), P()),
        check_vma=False,
    )
    return jax.jit(fn)


def run_distributed(
    query: JoinQuery,
    data: dict[str, np.ndarray],
    plan: SharesSkewPlan,
    mesh: Mesh | None = None,
    axis_name: str = "shuffle",
    cap_factor: float = 3.0,
    route_cap_factor: float = 3.0,
    obs: Observability = NULL_OBS,
) -> JoinResult:
    """Run the plan as one MapReduce round on ``mesh`` (all devices by
    default): map, one ``all_to_all`` over ``axis_name``, reduce.

    The round is four phases, each a span on a tracing ``obs`` (DESIGN.md
    §10):

    * ``shuffle.stage`` — pad each relation to a multiple of the devices
      and copy rows and masks to the device (counts ``h2d_bytes``);
    * ``shuffle.lower`` — build ``distributed_program`` and lower and
      compile it explicitly (``.lower(...).compile()``: the executable a
      call of the jitted program would build, found in the persistent
      compilation cache where it is there);
    * ``shuffle.execute`` — run it and wait for its outputs;
    * ``shuffle.fetch`` — copy the outputs to the host and build the
      ``JoinResult`` (counts ``d2h_bytes``, ``comm_tuples`` summed over
      relations, ``max_load``, ``total_load``, ``reducers``, ``overflow``).
    """
    if not plan.residuals:  # some relation is empty -> join is empty
        return JoinResult(
            count=0,
            checksum=0,
            comm_tuples={r.name: 0 for r in query.relations},
            reducer_loads=np.zeros(0, dtype=np.int32),
            overflow=0,
        )
    if mesh is None:
        devs = np.array(jax.devices())
        mesh = Mesh(devs, (axis_name,))
    d = mesh.shape[axis_name]
    k = plan.total_reducers
    rel_order = [r.name for r in query.relations]
    with obs.span("shuffle.stage", cat="job") as span:
        staged = [
            _pad_shard(np.asarray(data[nm], dtype=np.int32), d) for nm in rel_order
        ]
        rows_in = tuple(jnp.asarray(rows) for rows, _ in staged)
        masks_in = tuple(jnp.asarray(mask) for _, mask in staged)
        jax.block_until_ready((rows_in, masks_in))
        span.set(h2d_bytes=sum(rows.nbytes + mask.nbytes for rows, mask in staged))

    with obs.span("shuffle.lower", cat="job"):
        program = distributed_program(
            query, plan, mesh, axis_name, cap_factor, route_cap_factor
        ).lower(rows_in, masks_in).compile()

    with obs.span("shuffle.execute", cat="job"):
        outs = jax.block_until_ready(program(rows_in, masks_in))

    with obs.span("shuffle.fetch", cat="job") as span:
        counts, checksum, comm, loads, overflow = (np.asarray(x) for x in outs)
        result = JoinResult(
            count=int(counts.astype(np.int64).sum()),
            checksum=int(np.uint32(np.int64(checksum) & 0xFFFFFFFF)),
            comm_tuples={nm: int(c) for nm, c in zip(rel_order, comm)},
            reducer_loads=loads[:k],
            overflow=int(overflow),
        )
        span.set(
            d2h_bytes=sum(x.nbytes for x in (counts, checksum, comm, loads, overflow)),
            comm_tuples=result.total_comm,
            max_load=result.max_load,
            total_load=int(result.reducer_loads.astype(np.int64).sum()),
            reducers=k,
            overflow=result.overflow,
        )
    return result
