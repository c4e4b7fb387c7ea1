"""What every plain reference shares: matmul precision, the AdamW update
with its warmup-cosine schedule, gradients over blocks of rows, and the
readings compared with the program.

A configuration file's ``train`` block states the optimizer; the
reference follows it (AdamW, arXiv:1711.05101: decoupled weight decay,
bias-corrected moments, global-norm clipping before the update; linear
warmup, then a cosine to a tenth of the peak rate).
"""
from __future__ import annotations

import math
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
ROWS_PER_BLOCK = 1    # rows whose gradient is taken at once: the scan over
                      # them keeps the reference's peak to one row's


class Matmul:
    """``einsum`` in float32 at full precision.  ``low`` names a dtype
    (``float8_e4m3fn``) that every operand is rounded to first: the
    control, computed one precision below the configuration's bfloat16."""

    def __init__(self, low: Optional[str] = None):
        self.low = jnp.dtype(low) if low else None

    def round(self, x: jax.Array) -> jax.Array:
        if self.low is None:
            return x.astype(F32)
        return x.astype(self.low).astype(F32)

    def __call__(self, eq: str, a: jax.Array, b: jax.Array) -> jax.Array:
        return jnp.einsum(eq, self.round(a), self.round(b), precision=HIGHEST,
                          preferred_element_type=F32)


def lr_at(step, hp: Dict, total_steps: int):
    """Learning rate of the update that step ``step`` (0-based) makes."""
    step = jnp.asarray(step, F32)
    warm = jnp.minimum(1.0, (step + 1) / max(hp["warmup_steps"], 1))
    span = max(total_steps - hp["warmup_steps"], 1)
    prog = jnp.clip((step - hp["warmup_steps"]) / span, 0.0, 1.0)
    return hp["learning_rate"] * warm * (0.1 + 0.9 * 0.5 * (1 + jnp.cos(math.pi * prog)))


def leaf_norms(tree) -> Dict[str, jax.Array]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))
            for p, x in flat}


def make_step(loss_sum: Callable, hp: Dict, total_steps: int):
    """One AdamW step of the full batch.  ``loss_sum(params, tokens,
    labels) -> (sum of token losses, token count)`` over one block of
    ``ROWS_PER_BLOCK`` rows; the batch's loss is the sum over blocks over
    the count."""

    def grads(params, tokens, labels):
        b, s = tokens.shape
        nb = b // ROWS_PER_BLOCK
        tb = tokens.reshape(nb, ROWS_PER_BLOCK, s)
        lb = labels.reshape(nb, ROWS_PER_BLOCK, s)
        zero = jax.tree_util.tree_map(jnp.zeros_like, params)

        def body(acc, xs):
            (ls, cnt), g = jax.value_and_grad(loss_sum, has_aux=True)(
                params, xs[0], xs[1])
            return (acc[0] + ls, acc[1] + cnt,
                    jax.tree_util.tree_map(jnp.add, acc[2], g)), None

        (ls, cnt, g), _ = jax.lax.scan(
            body, (jnp.zeros((), F32), jnp.zeros((), F32), zero), (tb, lb))
        return ls / cnt, jax.tree_util.tree_map(lambda x: x / cnt, g)

    def step(params, m, v, count, step_idx, tokens, labels):
        loss, g = grads(params, tokens, labels)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                             for x in jax.tree_util.tree_leaves(g)))
        clip = jnp.minimum(1.0, hp["grad_clip"] / jnp.maximum(gnorm, 1e-9))
        g = jax.tree_util.tree_map(lambda x: x * clip, g)
        count = count + 1
        b1, b2, eps, wd = hp["beta1"], hp["beta2"], hp["eps"], hp["weight_decay"]
        lr = lr_at(step_idx, hp, total_steps)
        m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
        v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
        bc1 = 1 - b1 ** count.astype(F32)
        bc2 = 1 - b2 ** count.astype(F32)
        params = jax.tree_util.tree_map(
            lambda p, m, v: p - lr * ((m / bc1) / (jnp.sqrt(v / bc2) + eps)
                                      + wd * p), params, m, v)
        return params, m, v, count, loss, gnorm, leaf_norms(g)

    return jax.jit(step, donate_argnums=(0, 1, 2))


def train_readings(ref, model: Dict, hp: Dict, total_steps: int, seed: int,
                   batches: List, mm: Matmul) -> Dict:
    """Follow ``len(batches)`` steps from the seed's weights.

    Returns the loss and the global norm of the gradient (before clipping)
    of each step, the norm of each leaf of the first step's clipped
    gradient (what the optimizer gets), and the norm of each leaf's change
    over all the steps."""
    params = ref.init_params(model, seed)
    p0 = jax.tree_util.tree_map(np.asarray, params)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    count = jnp.zeros((), jnp.int32)
    step = make_step(lambda p, t, l: ref.loss_sum(p, t, l, model, mm), hp,
                     total_steps)
    losses, grad_norms, grad1 = [], [], None
    for i, (tokens, labels) in enumerate(batches):
        params, m, v, count, loss, gnorm, gn = step(
            params, m, v, count, i, jnp.asarray(tokens), jnp.asarray(labels))
        losses.append(float(loss))
        grad_norms.append(float(gnorm))
        if grad1 is None:
            grad1 = {k: float(x) for k, x in gn.items()}
    del m, v
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    p0_flat = dict((jax.tree_util.keystr(p), x) for p, x in
                   jax.tree_util.tree_flatten_with_path(p0)[0])
    change = {}
    for p, x in flat:
        k = jax.tree_util.keystr(p)
        d = np.asarray(x, np.float64) - p0_flat[k].astype(np.float64)
        change[k] = float(np.sqrt(np.sum(d * d)))
    return {"losses": losses, "grad_norms": grad_norms, "grad1": grad1,
            "change": change}
