"""Hierarchical aggregation as an explicit shard_map collective schedule.

The EEC-NET tree maps onto the production mesh: the 'data' axis plays the
edge tier (each edge server aggregates its clients' updates) and the 'pod'
axis plays the cloud tier (the cloud aggregates edge aggregates). The
GSPMD train_step gets the same result through a single fused all-reduce;
this module expresses the paper's TWO-STAGE schedule explicitly with
jax.shard_map + lax collectives so that

  * per-tier traffic is individually schedulable and measurable
    (HierFAVG's Table-VII decomposition at LM scale), and
  * tier-local variants (κ2 > 1: edge-only sync rounds between cloud
    aggregations) are expressible.

Semantics (tested vs the flat global mean):
  hier_grad_mean: per-microbatch gradient contributions, batch-sharded over
  ('pod','data'), reduced in two stages — psum over 'data' (edge tier)
  then psum over 'pod' (cloud tier) — and returned replicated.
"""
from __future__ import annotations

import jax
from jax import shard_map
from jax.sharding import PartitionSpec as P


def _data_axes(mesh, edge_axis, cloud_axis):
    return tuple(a for a in (cloud_axis, edge_axis) if a in mesh.axis_names)


def hier_grad_mean(tree, mesh, *, edge_axis: str = "data", cloud_axis: str = "pod"):
    """Global mean of batch-leading pytree leaves via the two-stage schedule.

    tree leaves: (B, ...) with B sharded over the (pod, data) axes.
    Stage 1: local mean within the shard (a client group's aggregate);
    Stage 2: psum over `edge_axis` (edge aggregation);
    Stage 3: psum over `cloud_axis` (cloud aggregation).
    Returns leaves of shape (...) — replicated, exactly the global mean.
    """
    axes = _data_axes(mesh, edge_axis, cloud_axis)
    if not axes:
        return jax.tree.map(lambda x: x.mean(0), tree)
    n_groups = 1
    for a in axes:
        n_groups *= mesh.shape[a]

    in_specs = jax.tree.map(lambda _: P(axes), tree)
    out_specs = jax.tree.map(lambda _: P(), tree)

    def staged(t):
        local = jax.tree.map(lambda x: x.mean(0), t)  # client-group mean
        if edge_axis in mesh.axis_names:  # edge tier
            local = jax.tree.map(lambda x: jax.lax.psum(x, edge_axis), local)
        if cloud_axis in mesh.axis_names:  # cloud tier
            local = jax.tree.map(lambda x: jax.lax.psum(x, cloud_axis), local)
        return jax.tree.map(lambda x: x / n_groups, local)

    fn = shard_map(staged, mesh=mesh, in_specs=(in_specs,), out_specs=out_specs)
    return fn(tree)


def edge_only_mean(tree, mesh, *, edge_axis: str = "data", cloud_axis: str = "pod"):
    """κ2 > 1 rounds: aggregate within the edge tier only; each pod keeps
    its own edge-tier aggregate (the cloud sees it at the next cloud round).
    Leaves: (B, ...) batch-sharded as in hier_grad_mean; the output is
    replicated within each pod but differs across pods."""
    axes = _data_axes(mesh, edge_axis, cloud_axis)
    if edge_axis not in mesh.axis_names:
        return jax.tree.map(lambda x: x.mean(0), tree)
    n_edge = mesh.shape[edge_axis]

    in_specs = jax.tree.map(lambda _: P(axes), tree)
    pod_spec = (cloud_axis,) if cloud_axis in mesh.axis_names else ()
    # output replicated over 'data', still distinct per pod: put the pod
    # axis on a length-n_pod leading dim so the caller can inspect per-pod
    out_specs = jax.tree.map(lambda _: P(pod_spec), tree)

    def staged(t):
        local = jax.tree.map(lambda x: x.mean(0), t)
        local = jax.tree.map(
            lambda x: jax.lax.psum(x, edge_axis) / n_edge, local
        )
        return jax.tree.map(lambda x: x[None] if pod_spec else x, local)

    fn = shard_map(staged, mesh=mesh, in_specs=(in_specs,), out_specs=out_specs)
    return fn(tree)
