"""In-graph pipeline parallelism over the ``pp`` mesh axis.

The reference runs pipelines with a C++ interpreter thread per stage
(SectionWorker 1F1B, framework/section_worker.cc:143-181) and NCCL P2P ops
at the cuts. Under XLA there is no interpreter to schedule — the pipeline
must live INSIDE the compiled program (SURVEY §7 hard part b). This module
implements the idiomatic TPU form:

* stage weights are stacked on a leading axis sharded over ``pp``;
* one ``lax.scan`` over clock ticks runs every stage in parallel (SPMD),
  with ``lax.ppermute`` rotating activations one ICI neighbor per tick —
  the fill/steady/drain schedule (GPipe-style);
* ``jax.grad`` through the scan yields the backward pipeline for free
  (reverse ticks, reversed ppermute); per-tick rematerialisation keeps
  activation memory at one microbatch per stage, and XLA's latency-hiding
  scheduler overlaps the ppermute with the next tick's compute — which is
  the property 1F1B hand-scheduling buys on GPUs.

Shape contract: microbatches [n_micro, micro_bs, ...]; every stage maps
[micro_bs, d] → [micro_bs, d] (homogeneous stages — stack your transformer
blocks; first/last stage embeddings/heads live outside the pipelined body).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["pipeline_apply", "stack_stage_params"]


def stack_stage_params(per_stage_params):
    """List of per-stage pytrees → one pytree with a leading stage axis
    (shard it over 'pp')."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                  *per_stage_params)


def pipeline_apply(stage_fn: Callable, stacked_params, micro_inputs,
                   axis_name: str = "pp", micro_aux=None):
    """Run the pipelined forward inside shard_map.

    stage_fn(params_one_stage, x) -> y, pure, same shape in/out — or
    stage_fn(params, x, aux) when ``micro_aux`` is given.
    stacked_params: pytree with leading stage axis, arriving SHARDED over
    ``axis_name`` (leading dim 1 per device inside shard_map).
    micro_inputs: [n_micro, micro_bs, ...] replicated across pp.
    micro_aux: optional pytree of [n_micro, ...] per-microbatch side
    inputs (e.g. attention masks) consumed by EVERY stage; stage s at
    tick t reads the aux of the microbatch it is processing (t - s).

    Returns [n_micro, micro_bs, ...]: outputs of the LAST stage in
    microbatch order (replicated via final broadcast).
    """
    n_stages = lax.axis_size(axis_name)
    stage_id = lax.axis_index(axis_name)
    n_micro = micro_inputs.shape[0]
    leading = {x.shape[0] for x in
               jax.tree_util.tree_leaves(stacked_params)}
    if leading != {1}:
        raise ValueError(
            f"pipeline_apply: stacked stage count must equal the "
            f"'{axis_name}' mesh axis size (got local leading dims "
            f"{sorted(leading)}; shard the stage axis over '{axis_name}')")
    local_params = jax.tree_util.tree_map(lambda x: x[0], stacked_params)
    ticks = n_micro + n_stages - 1
    perm_fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def tick(carry, t):
        buf, outputs = carry
        # stage 0 ingests microbatch t (zeros past the fill phase)
        fresh = jnp.where(t < n_micro,
                          micro_inputs[jnp.minimum(t, n_micro - 1)],
                          jnp.zeros_like(micro_inputs[0]))
        x = jnp.where(stage_id == 0, fresh, buf)
        if micro_aux is not None:
            mb_idx = jnp.clip(t - stage_id, 0, n_micro - 1)
            aux = jax.tree_util.tree_map(
                lambda a: lax.dynamic_index_in_dim(a, mb_idx, 0,
                                                   keepdims=False),
                micro_aux)
            y = stage_fn(local_params, x, aux)
        else:
            y = stage_fn(local_params, x)
        # last stage emits microbatch t-(n_stages-1) at tick t
        out_idx = t - (n_stages - 1)
        is_out = (out_idx >= 0) & (stage_id == n_stages - 1)
        outputs = lax.dynamic_update_index_in_dim(
            outputs,
            jnp.where(is_out, y, lax.dynamic_index_in_dim(
                outputs, jnp.maximum(out_idx, 0), 0, keepdims=False)),
            jnp.maximum(out_idx, 0), 0)
        # rotate activations one neighbor down the ring
        buf = lax.ppermute(y, axis_name, perm_fwd)
        return (buf, outputs), None

    buf0 = jnp.zeros_like(micro_inputs[0])
    outs0 = jnp.zeros_like(micro_inputs)
    buf0 = lax.pcast(buf0, (axis_name,), to="varying")
    outs0 = lax.pcast(outs0, (axis_name,), to="varying")
    (buf, outputs), _ = lax.scan(
        jax.checkpoint(tick), (buf0, outs0), jnp.arange(ticks))
    # broadcast last stage's outputs to every pp rank (so the loss is
    # computable everywhere under SPMD)
    mask = (stage_id == n_stages - 1).astype(outputs.dtype)
    return lax.psum(outputs * mask, axis_name)
