"""GPipe-style pipeline parallelism over a mesh axis (DESIGN.md 6, PP).

A stage function ``fn(stage_params, x) -> x`` is mapped over ``n_stages``
ranks of a mesh axis (the DCN ``pod`` axis in the production mesh: PP is
the bandwidth-tolerant parallelism to cross pods with -- one activation
hop per microbatch per boundary).  Microbatches stream through the
classic GPipe schedule: ``T = n_micro + n_stages - 1`` ticks, rank r
computes microbatch ``t - r`` at tick ``t``, activations hop ranks via
``lax.ppermute`` (whose transpose is the reverse permute, so ``jax.grad``
through the pipeline yields the reverse-schedule backward for free).

Bubble fraction = (n_stages - 1) / T, the standard GPipe trade; the test
asserts exact equality with the sequential stack and gradient agreement.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_fn(fn, mesh, axis: str, n_micro: int):
    """Build a pipelined apply: (stacked_params, x) -> y.

    stacked_params: pytree with leading [n_stages] axis (stage r's slice
    lives on rank r); x: [n_micro, mb, ...] microbatched input.
    Returns y: [n_micro, mb, ...] (the last stage's outputs, replicated).
    """
    n_stages = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]

    def per_rank(params_stage, x_micro):
        # params_stage: leaves [1, ...] (this rank's stage); x replicated
        params_local = jax.tree.map(lambda a: a[0], params_stage)
        rank = jax.lax.axis_index(axis)
        T = n_micro + n_stages - 1
        x0 = x_micro[0]
        # carries start rank-varying (scan VMA typing)
        buf = jax.lax.pcast(jnp.zeros_like(x0), (axis,), to="varying")
        outs = jax.lax.pcast(
            jnp.zeros((n_micro,) + x0.shape, x0.dtype), (axis,),
            to="varying")
        perm_fwd = [(i, i + 1) for i in range(n_stages - 1)]

        def tick(carry, t):
            buf, outs = carry
            micro_idx = jnp.clip(t - rank, 0, n_micro - 1)
            first_in = jax.lax.dynamic_index_in_dim(
                x_micro, jnp.clip(t, 0, n_micro - 1), keepdims=False)
            inp = jnp.where(rank == 0, first_in, buf)
            y = fn(params_local, inp)
            active = (t - rank >= 0) & (t - rank < n_micro)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # stash output if we are the last stage and active
            store = active & (rank == n_stages - 1)
            upd = jnp.where(store, y, jax.lax.dynamic_index_in_dim(
                outs, micro_idx, keepdims=False))
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, upd, micro_idx, 0)
            # hop the activation to the next rank
            buf = jax.lax.ppermute(y, axis, perm_fwd)
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(T))
        # replicate final outputs to every rank (psum of one-hot owner)
        owner = (rank == n_stages - 1).astype(outs.dtype)
        outs = jax.lax.psum(outs * owner, axis)
        return outs

    from repro.launch.sharding import manual_shard_map
    # fully manual (auto_rest=False): the per-rank body is local compute +
    # pod collectives, so unmentioned mesh axes just compute redundantly on
    # replicated inputs.
    return manual_shard_map(
        per_rank, mesh, {axis},
        (P(axis), P()),
        P(),
        auto_rest=False,
    )


def stack_stages(per_stage_params: list):
    """list of per-stage pytrees -> stacked pytree with leading stage axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage_params)


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
