"""Pipeline parallelism: GPipe-style microbatching over a "pipe" mesh axis.

Parallelism the reference entirely lacks (SURVEY.md §2.8 lists PP absent).
TPU-first SPMD design — instead of per-stage processes with P2P sends (the
GPU/NCCL shape), every device runs the SAME program under `shard_map`:

- the stacked stage dim of the layer params is sharded over "pipe", so each
  device holds exactly its stage's weights (no weight broadcast);
- a single activation "slot" per device circulates via `lax.ppermute`
  (neighbor exchange over ICI) once per tick;
- `lax.scan` over M + n - 1 ticks: stage 0 ingests microbatch t, stage n-1
  emits microbatch t-(n-1); the scan is reverse-differentiable, so the
  backward pipeline falls out of autodiff (ppermute transposes to the
  reversed ring) — no hand-written 1F1B schedule needed;
- all shapes are static; the bubble is the usual (n-1)/(M+n-1) fraction.

`pipeline_apply` is the generic schedule; `PipelinedLM` is a small
functional decoder (embed -> pipelined residual blocks -> head) used by the
multi-chip dry run and tests.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np


def pipeline_apply(
    stage_fn: Callable,
    stage_params,
    x: jnp.ndarray,
    mesh,
    axis: str = "pipe",
    num_microbatches: Optional[int] = None,
):
    """Run ``x`` through ``n = mesh.shape[axis]`` pipeline stages.

    stage_fn(params, act) -> act: one stage's compute; must preserve the
        activation's shape/dtype (residual-block style).
    stage_params: pytree whose leaves are stacked [n, ...] on dim 0 (stage i
        uses leaf[i]); shard them with `stage_param_sharding`.
    x: [B, ...] global batch; B must divide into ``num_microbatches``
        (default n) equal microbatches.
    """
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]
    M = int(num_microbatches or n)
    B = x.shape[0]
    if B % M:
        raise ValueError(
            "Batch {} must divide into {} microbatches".format(B, M))
    x_mb = x.reshape((M, B // M) + x.shape[1:])

    def local_fn(params_local, x_mb):
        idx = jax.lax.axis_index(axis)
        # shard_map hands each device a [1, ...] slice of the stacked stage
        # dim; drop it to get this stage's params.
        params = jax.tree_util.tree_map(lambda p: p[0], params_local)
        state0 = jnp.zeros_like(x_mb[0])

        def tick(state, t):
            inp = jax.lax.dynamic_index_in_dim(
                x_mb, jnp.clip(t, 0, M - 1), axis=0, keepdims=False)
            state = jnp.where(idx == 0, inp, state)
            out = stage_fn(params, state)
            # Rotate forward one stage per tick (ICI neighbor exchange);
            # stage n-1 -> 0 wraps but is overwritten by fresh input.
            nxt = jax.lax.ppermute(
                out, axis, [(i, (i + 1) % n) for i in range(n)])
            return nxt, out

        _, emits = jax.lax.scan(tick, state0, jnp.arange(M + n - 1))
        # On the last stage, microbatch m leaves the pipe at tick m + n - 1.
        y_local = emits[n - 1:]
        # Broadcast the last stage's outputs to every device (replicated
        # result lets the unsharded head/loss follow under plain GSPMD).
        return jax.lax.psum(
            jnp.where(idx == n - 1, y_local, jnp.zeros_like(y_local)), axis)

    stage_spec = jax.tree_util.tree_map(
        lambda p: P(axis, *([None] * (np.ndim(p) - 1))), stage_params)
    # Shard the per-microbatch batch dim over any data axes so those axes do
    # real data parallelism instead of replicated identical stage compute
    # (the pipeline is batch-elementwise, so each data shard pipelines its
    # own slice independently).
    data_axes = tuple(a for a in ("data", "fsdp") if a in mesh.axis_names)
    dp = int(np.prod([mesh.shape[a] for a in data_axes])) if data_axes else 1
    if data_axes and (B // M) % dp == 0:
        x_spec = P(None, data_axes, *([None] * (x_mb.ndim - 2)))
    else:
        x_spec = P()
    out = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(stage_spec, x_spec), out_specs=x_spec,
        check_vma=False,
    )(stage_params, x_mb)
    return out.reshape((B,) + out.shape[2:])


def pipeline_1f1b_grads(
    stage_fn: Callable,
    loss_fn: Callable,
    stage_params,
    x: jnp.ndarray,
    targets,
    mesh,
    axis: str = "pipe",
    num_microbatches: Optional[int] = None,
):
    """One-forward-one-backward (PipeDream-flush) pipelined TRAINING step:
    returns ``(mean_loss, stage_param_grads)`` directly.

    Why a separate entry point: `pipeline_apply` + autodiff IS GPipe — the
    whole forward flushes before the backward starts, so every microbatch's
    scan residuals stay live and activation memory grows with M. True 1F1B
    starts each microbatch's backward as soon as the last stage finishes
    its forward, which means the loss must be computed INSIDE the pipeline
    (a custom_vjp around `pipeline_apply` could never reorder fwd/bwd
    across its own boundary). In-flight activations are bounded by n — the
    stash here is a static [n, ...] ring buffer — so at EQUAL activation
    memory 1F1B affords ~M/n× more microbatches, and the bubble fraction
    (n-1)/(M+n-1) shrinks accordingly. Inputs are re-staged through the
    stash and the stage forward is recomputed in the backward sub-step
    (remat-style), the standard 1F1B memory/FLOPs trade.

    Schedule (0-based stage i, microbatch m, n stages, M microbatches,
    one slot = one F and one B sub-step, T = 2(M+n-1) slots):

    - warmup forwards (m < n - i):  F_m(i) = i + m
    - steady forwards  (m >= n-i):  F_m(i) = 2m + i
    - backwards:                    B_m(i) = 2n - 1 - i + 2m

    Backward grads arrive exactly at their consumption slot
    (B_m(i) = B_m(i+1) + 1). Forward activations arrive just-in-time too
    EXCEPT each sender's last warmup microbatch (m = n-i-1), which lands
    n-i-1 slots early — so arrivals are stashed into the [n, ...] ring
    buffer keyed by microbatch (mod n) at arrival time, and the same
    buffer doubles as the backward-recompute stash (entry m is written at
    arrival <= F_m(i) and last read at B_m(i), strictly before microbatch
    m+n's arrival overwrites it).

    stage_fn(params, act) -> act          (shape-preserving, as GPipe)
    loss_fn(act, target) -> scalar        (applied per microbatch on the
                                           last stage's output)
    targets: [B, ...] aligned with x's batch dim (microbatched the same
        way); pass e.g. next-token labels.
    """
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]
    M = int(num_microbatches or n)
    B = x.shape[0]
    if B % M:
        raise ValueError(
            "Batch {} must divide into {} microbatches".format(B, M))
    x_mb = x.reshape((M, B // M) + x.shape[1:])
    t_mb = targets.reshape((M, B // M) + targets.shape[1:])
    T = 2 * (M + n - 1)

    def local_fn(params_local, x_mb, t_mb):
        idx = jax.lax.axis_index(axis)
        params = jax.tree_util.tree_map(lambda p: p[0], params_local)
        mb_shape = x_mb.shape[1:]

        def fwd_mb(t, stage=None):
            """(active, m) for ``stage``'s F sub-step at slot t."""
            i = idx if stage is None else stage
            d = t - i
            warm = (i <= t) & (t < n) & (d < M)
            m_steady = d // 2
            steady = (d >= 0) & (d % 2 == 0) & (m_steady >= n - i) \
                & (m_steady < M)
            m = jnp.where(warm, d, m_steady)
            return warm | steady, jnp.clip(m, 0, M - 1)

        def bwd_mb(t):
            r = t - (2 * n - 1 - idx)
            m = r // 2
            active = (r >= 0) & (r % 2 == 0) & (m < M)
            return active, jnp.clip(m, 0, M - 1)

        def f_with_params(p, a):
            return stage_fn(p, a)

        def slot(carry, t):
            stash, act_in, grad_in, dy_pending, loss_sum, gacc = carry

            # ---- stash the activation that just arrived ---------------
            # act_in was sent by stage idx-1 at slot t-1; its microbatch
            # index comes from the SENDER's schedule.
            in_active, m_in = fwd_mb(t - 1, stage=idx - 1)
            stash = jnp.where(
                in_active & (idx > 0),
                jax.lax.dynamic_update_index_in_dim(
                    stash, act_in, m_in % n, axis=0),
                stash)

            # ---- forward sub-step -------------------------------------
            f_active, m_f = fwd_mb(t)
            inp = jnp.where(
                idx == 0,
                jax.lax.dynamic_index_in_dim(x_mb, m_f, 0, keepdims=False),
                jax.lax.dynamic_index_in_dim(
                    stash, m_f % n, axis=0, keepdims=False))
            # Stage 0 ring-buffers its OWN input for the backward recompute
            # (other stages already stashed it at arrival).
            stash = jnp.where(
                f_active & (idx == 0),
                jax.lax.dynamic_update_index_in_dim(
                    stash, inp, m_f % n, axis=0),
                stash)
            out = stage_fn(params, inp)
            # Last stage: per-microbatch loss + output cotangent, consumed
            # by this stage's OWN backward next slot (B_m = F_m + 1 there).
            tgt = jax.lax.dynamic_index_in_dim(t_mb, m_f, 0, keepdims=False)
            loss_val, dy_new = jax.value_and_grad(loss_fn)(out, tgt)
            is_last = idx == n - 1
            loss_sum = loss_sum + jnp.where(f_active & is_last, loss_val, 0.0)
            dy_pending_next = jnp.where(f_active & is_last, dy_new, dy_pending)

            # ---- backward sub-step ------------------------------------
            b_active, m_b = bwd_mb(t)
            inp_b = jax.lax.dynamic_index_in_dim(
                stash, m_b % n, axis=0, keepdims=False)
            g_out = jnp.where(is_last, dy_pending, grad_in)
            _, vjp_fn = jax.vjp(f_with_params, params, inp_b)
            dparams, dx = vjp_fn(g_out)
            gacc = jax.tree_util.tree_map(
                lambda acc, d: jnp.where(b_active, acc + d, acc), gacc, dparams)

            # ---- neighbor exchanges (one hop each way per slot) -------
            act_next = jax.lax.ppermute(
                out, axis, [(i, (i + 1) % n) for i in range(n)])
            grad_next = jax.lax.ppermute(
                jnp.where(b_active, dx, jnp.zeros_like(dx)), axis,
                [(i, (i - 1) % n) for i in range(n)])
            return (stash, act_next, grad_next, dy_pending_next,
                    loss_sum, gacc), None

        zeros = jnp.zeros(mb_shape, x_mb.dtype)
        carry0 = (
            jnp.zeros((n,) + mb_shape, x_mb.dtype),  # recompute stash
            zeros,                                   # incoming activation
            zeros,                                   # incoming out-grad
            zeros,                                   # last stage's pending dy
            jnp.zeros((), jnp.float32),
            jax.tree_util.tree_map(lambda p: jnp.zeros_like(p), params),
        )
        (_, _, _, _, loss_sum, gacc), _ = jax.lax.scan(
            slot, carry0, jnp.arange(T))
        # Only the last stage accumulated loss; share it around the ring.
        loss = jax.lax.psum(loss_sum, axis) / M
        data_axes = tuple(a for a in ("data", "fsdp") if a in mesh.axis_names)
        if data_axes:
            loss = jax.lax.pmean(loss, data_axes)
            gacc = jax.tree_util.tree_map(
                lambda g: jax.lax.pmean(g, data_axes), gacc)
        # Mean-of-microbatch-means, matching `mean_m loss_fn(y_m, t_m)`.
        gacc = jax.tree_util.tree_map(lambda g: (g / M)[None], gacc)
        return loss, gacc

    stage_spec = jax.tree_util.tree_map(
        lambda p: P(axis, *([None] * (np.ndim(p) - 1))), stage_params)
    data_axes = tuple(a for a in ("data", "fsdp") if a in mesh.axis_names)
    dp = int(np.prod([mesh.shape[a] for a in data_axes])) if data_axes else 1
    if data_axes and (B // M) % dp == 0:
        mb_spec = P(None, data_axes, *([None] * (x_mb.ndim - 2)))
        tgt_spec = P(None, data_axes, *([None] * (t_mb.ndim - 2)))
    else:
        mb_spec, tgt_spec = P(), P()
    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(stage_spec, mb_spec, tgt_spec),
        out_specs=(P(), stage_spec),
        check_vma=False,
    )(stage_params, x_mb, t_mb)


def stage_param_sharding(mesh, stage_params, axis: str = "pipe"):
    """NamedShardings placing each leaf's stacked stage dim on ``axis``."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.tree_util.tree_map(
        lambda p: NamedSharding(mesh, P(axis, *([None] * (np.ndim(p) - 1)))),
        stage_params)


class PipelinedLM:
    """Minimal functional decoder for the pp path: embedding -> n_stages of
    residual SwiGLU blocks (stacked + pipelined) -> head.

    Pure functions over an explicit param pytree (no flax) so the stacked
    stage dim is first-class; init places params directly into their
    shardings when a mesh is given.
    """

    def __init__(self, vocab_size: int, hidden_dim: int, intermediate_dim: int,
                 num_stages: int, layers_per_stage: int = 1,
                 dtype: Any = jnp.bfloat16):
        self.vocab_size = vocab_size
        self.hidden_dim = hidden_dim
        self.intermediate_dim = intermediate_dim
        self.num_stages = num_stages
        self.layers_per_stage = layers_per_stage
        self.dtype = dtype

    def init(self, rng, mesh=None, axis: str = "pipe"):
        V, D, F = self.vocab_size, self.hidden_dim, self.intermediate_dim
        n, L = self.num_stages, self.layers_per_stage
        ks = jax.random.split(rng, 5)
        scale = lambda fan_in: 1.0 / np.sqrt(fan_in)  # noqa: E731
        params = {
            "embed": jax.random.normal(ks[0], (V, D), jnp.float32) * 0.02,
            "stages": {
                "w_gate": jax.random.normal(ks[1], (n, L, D, F)) * scale(D),
                "w_up": jax.random.normal(ks[2], (n, L, D, F)) * scale(D),
                "w_down": jax.random.normal(ks[3], (n, L, F, D)) * scale(F),
            },
            "head": jax.random.normal(ks[4], (D, V), jnp.float32) * 0.02,
        }
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            shardings = {
                "embed": NamedSharding(mesh, P()),
                "stages": stage_param_sharding(mesh, params["stages"], axis),
                "head": NamedSharding(mesh, P()),
            }
            params = jax.tree_util.tree_map(jax.device_put, params, shardings)
        return params

    def stage_fn(self, stage_params, x):
        """L residual SwiGLU blocks; [mb, S, D] -> [mb, S, D]."""

        def block(x, layer):
            w_gate, w_up, w_down = layer
            h = jnp.dot(x, w_gate.astype(self.dtype))
            u = jnp.dot(x, w_up.astype(self.dtype))
            y = jnp.dot(jax.nn.silu(h) * u, w_down.astype(self.dtype))
            return x + y, None

        layers = (stage_params["w_gate"], stage_params["w_up"],
                  stage_params["w_down"])
        x, _ = jax.lax.scan(block, x, layers)
        return x

    def apply(self, params, tokens, mesh, axis: str = "pipe",
              num_microbatches: Optional[int] = None):
        x = params["embed"].astype(self.dtype)[tokens]
        x = pipeline_apply(
            lambda p, a: self.stage_fn(p, a), params["stages"], x, mesh,
            axis=axis, num_microbatches=num_microbatches)
        return jnp.dot(x, params["head"].astype(self.dtype)).astype(jnp.float32)

    def apply_sequential(self, params, tokens):
        """Reference forward with NO pipelining (correctness oracle)."""
        x = params["embed"].astype(self.dtype)[tokens]
        for i in range(self.num_stages):
            stage = jax.tree_util.tree_map(lambda p: p[i], params["stages"])
            x = self.stage_fn(stage, x)
        return jnp.dot(x, params["head"].astype(self.dtype)).astype(jnp.float32)
