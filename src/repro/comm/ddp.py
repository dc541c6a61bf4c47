"""Explicit data-parallel S-SGD train step (the paper's Algorithm 1).

This is the *paper-reproduction* runtime: parameters replicated on the
``data`` axis (pure DP), batch sharded, gradients synchronized by an
explicit, policy-selected collective schedule — so the lowered HLO
shows exactly the framework differences of §IV-C (one fused all-reduce
at the end for CNTK vs. per-layer all-reduces inside the backward loop
for WFBP vs. fused buckets).

The production runtime (``repro.launch.train``) instead uses SPMD
sharding (FSDP/TP) where XLA places the collectives.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.comm import sync as S
from repro.models import transformer as T
from repro.models.common import ModelConfig
from repro.optim.sgd import Optimizer, global_norm


def make_ddp_train_step(cfg: ModelConfig, optimizer: Optimizer, mesh: Mesh,
                        sync_policy: str = "wfbp", dp_axis: str = "data",
                        bucket_bytes: float = S.DEFAULT_BUCKET_BYTES,
                        remat: bool = False):
    """Returns ``step(params, opt_state, batch) -> (params, opt_state,
    metrics)`` as a shard_map'd jitted function.

    ``sync_policy``: one of ``repro.comm.sync.SYNC_POLICIES``.
    """
    dp_axes = (dp_axis,)
    world = mesh.shape[dp_axis]

    def local_step(params, opt_state, batch):
        hook = (S.wfbp_param_hook(dp_axes, float(world))
                if sync_policy == "wfbp" else None)

        def loss(p):
            return T.loss_fn(cfg, p, batch["tokens"], batch["labels"],
                             remat=remat, param_hook=hook)

        (total, metrics), grads = jax.value_and_grad(loss, has_aux=True)(params)
        grads = S.sync_gradients(grads, sync_policy, dp_axes, bucket_bytes)
        # the losses are also averaged for reporting, as ONE f32[2]
        # collective: two scalar pmeans would be CSE'd into one whenever
        # the aux loss is a constant zero (dense models), so the step's
        # collective bytes would depend on the architecture
        total, loss = jax.lax.pmean(jnp.stack([total, metrics["loss"]]),
                                    dp_axes)
        new_params, new_opt = optimizer.update(grads, opt_state, params)
        out_metrics = {"loss": loss, "total_loss": total,
                       "grad_norm": global_norm(grads)}
        return new_params, new_opt, out_metrics

    batch_specs = {"tokens": P(dp_axis), "labels": P(dp_axis)}
    step = jax.shard_map(local_step, mesh=mesh,
                         in_specs=(P(), P(), batch_specs),
                         out_specs=(P(), P(), P()), check_vma=False)
    return jax.jit(step, donate_argnums=(0, 1))


def lower_ddp_step(cfg: ModelConfig, optimizer: Optimizer, mesh: Mesh,
                   sync_policy: str, batch_size: int, seq_len: int,
                   dp_axis: str = "data"):
    """Lower (no execute) for HLO inspection of collective placement."""
    import numpy as np

    params = jax.eval_shape(lambda k: T.init_lm(cfg, k),
                            jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(optimizer.init, params)
    batch = {"tokens": jax.ShapeDtypeStruct((batch_size, seq_len), jnp.int32),
             "labels": jax.ShapeDtypeStruct((batch_size, seq_len), jnp.int32)}
    step = make_ddp_train_step(cfg, optimizer, mesh, sync_policy,
                               dp_axis=dp_axis)
    return step.lower(params, opt_state, batch)
