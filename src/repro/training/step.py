"""Spliced training step: the paper's replica splicing as a compiled program.

The logical world size W is constant; the scheduler maps W logical ranks
onto P physical devices (splice factor s = W/P).  Inside the jitted step:

- ``lax.scan`` over the s time-slices — each iteration is one resident
  logical-rank group's forward/backward (the context switch of §5.1);
- gradients are accumulated locally across slices in f32 scratch (the
  device-proxy's local accumulation: the cross-device collective sees ONE
  rank per device);
- the optimizer update runs once per device after the last slice —
  squashing (§5.2.3) expressed structurally: there is simply no per-slice
  update to omit.

The same lowering artifact gives elasticity AND activation-memory control
(slices bound live activations), which is what the dry-run exercises on the
production mesh.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.configs.base import ModelConfig, TrainConfig
from repro.models import model_forward
from repro.optim.adamw import adamw_update, global_norm
from repro.optim.schedule import lr_schedule


def build_train_step(cfg: ModelConfig, tcfg: TrainConfig, splice: int = 1,
                     with_barrier: bool = False, mesh: Optional[Mesh] = None,
                     data_axes: Tuple[str, ...] = ("data",)) -> Callable:
    """Returns train_step(state, batch[, barrier_flags]) -> (state, metrics).

    batch leaves have leading dim = global_batch; they are split into
    ``splice`` time-slices internally.
    """

    def split(batch: Dict) -> Dict:
        def r(a):
            g = a.shape[0]
            assert g % splice == 0, (g, splice)
            return a.reshape((splice, g // splice) + a.shape[1:])
        return jax.tree_util.tree_map(r, batch)

    def loss_fn(params, mb):
        loss, metrics = model_forward(params, mb, cfg, remat=tcfg.remat,
                                      remat_policy=tcfg.remat_policy)
        return loss, metrics

    def train_step(state, batch, barrier_flags=None):
        params = state["params"]
        mbs = split(batch)

        grad_zero = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)

        def slice_body(carry, mb):
            gacc, lacc = carry
            (loss, fwd), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, mb)
            gacc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32), gacc, grads)
            return (gacc, lacc + loss), fwd.get("moe_rows")

        if splice == 1:
            mb = jax.tree_util.tree_map(lambda a: a[0], mbs)
            (loss, fwd), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, mb)
            grads = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32),
                                           grads)
            lsum, rows = loss, fwd.get("moe_rows")
        else:
            (grads, lsum), rows = jax.lax.scan(
                slice_body, (grad_zero, jnp.zeros((), jnp.float32)), mbs)
            grads = jax.tree_util.tree_map(lambda g: g / splice, grads)
            if rows is not None:
                rows = jnp.sum(rows, axis=0)

        lr = lr_schedule(state["step"], tcfg)
        new_params, new_opt = adamw_update(params, grads, state["opt"], lr, tcfg)
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        metrics = {
            "loss": lsum / splice,
            "lr": lr,
            "grad_norm": global_norm(grads),
        }
        if rows is not None:
            # rows routed to each held expert per MoE block, all slices
            metrics["moe_rows"] = rows
        if with_barrier:
            # lazy import: core/__init__ imports elastic -> this module
            from repro.core.barrier_jax import meta_allreduce
            assert barrier_flags is not None
            metrics["barrier"] = meta_allreduce(barrier_flags, mesh, data_axes)
        return new_state, metrics

    return train_step
