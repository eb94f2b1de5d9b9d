"""GPipe-style pipeline parallelism over a ``stage`` mesh axis.

``gpipe_apply`` runs a stacked-stage function over microbatches with the
classic (M + S - 1)-tick schedule: activations hop stage→stage via
``ppermute`` inside ``shard_map``.  Stages hold their own parameter shard;
bubbles are masked compute.  This is the PP building block exercised by the
tests and available to the launcher for deep-stack configs (DESIGN.md §6).
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

STAGE_AXIS = "stage"


def gpipe_apply(stage_fn: Callable, stage_params, x_micro: jax.Array,
                mesh: Mesh, n_stages: int) -> jax.Array:
    """Run microbatches through a pipeline of stages.

    stage_fn(params_one_stage, x) -> y  (same shape as x)
    stage_params: pytree with leading stage axis (n_stages, ...)
    x_micro: (n_micro, mb, ...) microbatched input.
    Returns (n_micro, mb, ...) outputs of the final stage.
    """
    n_micro = x_micro.shape[0]
    ticks = n_micro + n_stages - 1

    def body(params_local, x_local):
        # params_local: (1, ...) this stage's params; x_local replicated
        params_l = jax.tree_util.tree_map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index(STAGE_AXIS)
        mb_shape = x_local.shape[1:]
        state = jnp.zeros(mb_shape, x_local.dtype)
        outputs = jnp.zeros_like(x_local)

        def tick(t, carry):
            state, outputs = carry
            # stage 0 injects microbatch t (while t < n_micro)
            inject = jax.lax.dynamic_index_in_dim(
                x_local, jnp.minimum(t, n_micro - 1), 0, keepdims=False)
            inp = jnp.where(stage == 0, inject, state)
            active = (t >= stage) & (t - stage < n_micro)
            out = stage_fn(params_l, inp)
            out = jnp.where(active, out, state)
            # last stage deposits its finished microbatch (index t - stage)
            mb_idx = jnp.clip(t - stage, 0, n_micro - 1)
            deposit = (stage == n_stages - 1) & active
            upd = jax.lax.dynamic_update_index_in_dim(
                outputs, out, mb_idx, 0)
            outputs = jnp.where(deposit, upd, outputs)
            # hop to the next stage
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            state = jax.lax.ppermute(out, STAGE_AXIS, perm)
            return state, outputs

        _, outputs = jax.lax.fori_loop(0, ticks, tick, (state, outputs))
        # only the last stage holds real deposits; replicate them so the
        # P() out_spec is well-defined on every shard
        outputs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outputs, 0.0), STAGE_AXIS)
        return outputs

    pspec = jax.tree_util.tree_map(lambda _: P(STAGE_AXIS), stage_params)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(pspec, P()),
                       out_specs=P(), check_vma=False)
    return fn(stage_params, x_micro)


def sequential_ref(stage_fn: Callable, stage_params, x_micro: jax.Array,
                   n_stages: int) -> jax.Array:
    """Oracle: run every microbatch through all stages sequentially."""
    def full(x):
        for s in range(n_stages):
            p = jax.tree_util.tree_map(lambda a: a[s], stage_params)
            x = stage_fn(p, x)
        return x
    return jax.vmap(full)(x_micro)
