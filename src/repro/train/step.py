"""Train-step factory: loss, grads (with optional microbatch accumulation
and remat via the model config), clipping, AdamW, schedules, MoE aux
losses. Also a shard_map manual-DP variant exercising ZeRO reduce-scatter
and int8 gradient compression (feature-flagged)."""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed import compression
from repro.optim import (adamw_init, adamw_update, clip_by_global_norm,
                         linear_warmup_linear_decay)


class TrainState(NamedTuple):
    params: object
    opt: object
    step: jax.Array


def init_train_state(params) -> TrainState:
    return TrainState(params=params, opt=adamw_init(params),
                      step=jnp.zeros((), jnp.int32))


def make_loss_fn(model, cfg):
    def loss_fn(params, batch):
        tokens = batch["tokens"]                      # (B, S+1)
        inp = {"tokens": tokens[:, :-1]}
        for k in ("image_embeds", "frames"):
            if k in batch:
                inp[k] = batch[k]
        targets = tokens[:, 1:]
        # the Pallas kernels have no VJP yet, so gradients take the XLA path
        logits, _, aux = model.apply(params, inp, mode="train", impl="xla")
        with jax.named_scope("loss"):
            logits = logits.astype(jnp.float32)
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, targets[..., None],
                                       axis=-1)[..., 0]
            mask = batch.get("loss_mask")
            if mask is None:
                loss = jnp.mean(nll)
            else:
                loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        metrics = {"ce_loss": loss}
        if aux:
            loss = (loss
                    + cfg.router_aux_weight * aux.get("load_balance", 0.0)
                    + cfg.router_z_weight * aux.get("router_z", 0.0))
            metrics.update({f"aux_{k}": v for k, v in aux.items()})
        metrics["loss"] = loss
        return loss, metrics

    return loss_fn


def make_train_step(model, cfg, tcfg):
    """Returns train_step(state, batch) -> (state, metrics). pjit-friendly:
    gradient sync/FSDP collectives come from the sharding annotations."""
    loss_fn = make_loss_fn(model, cfg)
    schedule = linear_warmup_linear_decay(tcfg.peak_lr, tcfg.steps,
                                          tcfg.warmup_frac)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    mb = tcfg.microbatches

    def compute_grads(params, batch):
        if mb == 1:
            (_, metrics), grads = grad_fn(params, batch)
            return grads, metrics

        def split(x):
            return x.reshape(mb, x.shape[0] // mb, *x.shape[1:])

        mbatch = {k: split(v) for k, v in batch.items()}

        def body(carry, mbat):
            acc, _ = carry
            (_, metrics), grads = grad_fn(params, mbat)
            acc = jax.tree_util.tree_map(jnp.add, acc, grads)
            return (acc, metrics), None

        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        m0 = {"loss": jnp.zeros((), jnp.float32),
              "ce_loss": jnp.zeros((), jnp.float32)}
        if cfg.ffn == "moe":
            m0.update(aux_load_balance=jnp.zeros((), jnp.float32),
                      aux_router_z=jnp.zeros((), jnp.float32))
        (grads, metrics), _ = jax.lax.scan(body, (zeros, m0), mbatch)
        grads = jax.tree_util.tree_map(lambda g: g / mb, grads)
        return grads, metrics

    def train_step(state: TrainState, batch):
        grads, metrics = compute_grads(state.params, batch)
        with jax.named_scope("optimizer"):
            grads, gnorm = clip_by_global_norm(grads, tcfg.grad_clip)
            lr = schedule(state.step)
            new_params, new_opt = adamw_update(
                grads, state.opt, state.params, lr=lr, b1=tcfg.b1,
                b2=tcfg.b2, weight_decay=tcfg.weight_decay)
        metrics = dict(metrics, grad_norm=gnorm, lr=lr)
        return TrainState(new_params, new_opt, state.step + 1), metrics

    return train_step


def make_manual_dp_train_step(model, cfg, tcfg, mesh, dp_axis: str = "data"):
    """shard_map manual-DP step: per-device grads + explicit sync so the
    gradient collective is OURS to choose — pmean (baseline) or int8
    compressed all-to-all reduce (tcfg.grad_compression == "int8").

    Params are replicated over dp_axis here (pure-DP demonstration path;
    production pjit path uses FSDP sharding instead)."""
    loss_fn = make_loss_fn(model, cfg)
    schedule = linear_warmup_linear_decay(tcfg.peak_lr, tcfg.steps,
                                          tcfg.warmup_frac)
    grad_fn = jax.grad(loss_fn, has_aux=True)
    dp = mesh.shape[dp_axis]

    def sync(grads):
        if tcfg.grad_compression == "int8":
            return compression.tree_int8_allreduce_mean(grads, dp_axis, dp)
        return compression.tree_psum_mean(grads, dp_axis)

    def sharded_grads(params, batch):
        grads, metrics = grad_fn(params, batch)
        grads = sync(grads)
        metrics = jax.tree_util.tree_map(
            lambda m: jax.lax.pmean(m, dp_axis), metrics)
        return grads, metrics

    def train_step(state: TrainState, batch):
        in_specs = (jax.tree_util.tree_map(lambda _: P(), state.params),
                    jax.tree_util.tree_map(lambda _: P(dp_axis), batch))
        out_specs = (jax.tree_util.tree_map(lambda _: P(), state.params),
                     {k: P() for k in ["loss", "ce_loss"]})
        grads, metrics = jax.shard_map(
            sharded_grads, mesh=mesh, in_specs=in_specs,
            out_specs=out_specs, check_vma=False)(state.params, batch)
        with jax.named_scope("optimizer"):
            grads, gnorm = clip_by_global_norm(grads, tcfg.grad_clip)
            lr = schedule(state.step)
            new_params, new_opt = adamw_update(
                grads, state.opt, state.params, lr=lr, b1=tcfg.b1,
                b2=tcfg.b2, weight_decay=tcfg.weight_decay)
        metrics = dict(metrics, grad_norm=gnorm, lr=lr)
        return TrainState(new_params, new_opt, state.step + 1), metrics

    return train_step
