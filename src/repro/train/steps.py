"""Jitted train/eval steps with grad-accumulation and compression hooks.

``make_train_step(model_cfg, train_cfg)`` builds::

    train_step(state, batch) -> (state, metrics)

* loss = model loss + MoE aux loss
* grad accumulation: ``lax.scan`` over ``microbatches`` leading-dim splits,
  accumulating fp32 grads (bounds activation memory for the 340B/52B cells)
* optional gradient compression round-trip (bf16 / int8+error-feedback)
* optimizer update (AdamW / Adafactor / SGD)

State is a plain dict pytree => trivially shardable and checkpointable.
"""
from __future__ import annotations

from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, TrainConfig
from repro.models import encdec, resnet, transformer
from repro.train import compression
from repro.train.optim import global_norm, make_optimizer


def loss_fn_for(cfg: ModelConfig) -> Callable:
    if cfg.family == "encdec":
        return lambda p, b: encdec.forward_train(p, b, cfg)
    if cfg.family == "resnet":
        raise ValueError("use make_resnet_train_step for the resnet family")
    return lambda p, b: transformer.forward_train(p, b, cfg)


def init_params_for(cfg: ModelConfig, key) -> Any:
    if cfg.family == "encdec":
        return encdec.init_encdec(key, cfg)
    if cfg.family == "resnet":
        return resnet.init_resnet(key, cfg)[0]
    return transformer.init_lm(key, cfg)


def init_train_state(cfg: ModelConfig, tcfg: TrainConfig, key) -> Dict[str, Any]:
    params = init_params_for(cfg, key)
    opt = make_optimizer(tcfg)
    state = {
        "params": params,
        "opt": opt.init(params),
        "step": jnp.zeros((), jnp.int32),
    }
    if tcfg.grad_compression == "int8_ef":
        state["ef"] = compression.init_error_feedback(params)
    return state


def make_train_step(cfg: ModelConfig, tcfg: TrainConfig) -> Callable:
    opt = make_optimizer(tcfg)
    loss_fn = loss_fn_for(cfg)
    M = max(tcfg.microbatches, 1)

    def compute_grads(params, batch):
        def total_loss(p, b):
            loss, aux = loss_fn(p, b)
            return loss + aux, (loss, aux)

        if M == 1:
            (tl, (loss, aux)), grads = jax.value_and_grad(total_loss, has_aux=True)(
                params, batch
            )
            return grads, loss, aux

        def micro(b):
            return jax.tree.map(lambda x: x.reshape(M, x.shape[0] // M, *x.shape[1:]), b)

        mbatch = micro(batch)

        def body(carry, mb):
            acc, lsum, asum = carry
            (tl, (loss, aux)), g = jax.value_and_grad(total_loss, has_aux=True)(
                params, mb
            )
            acc = jax.tree.map(lambda a, x: a + x.astype(jnp.float32), acc, g)
            return (acc, lsum + loss, asum + aux), None

        zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (gsum, lsum, asum), _ = jax.lax.scan(
            body, (zero, jnp.zeros(()), jnp.zeros(())), mbatch
        )
        grads = jax.tree.map(lambda g: g / M, gsum)
        return grads, lsum / M, asum / M

    def train_step(state, batch):
        params = state["params"]
        grads, loss, aux = compute_grads(params, batch)
        ef = state.get("ef")
        grads, new_ef = compression.apply_compression(grads, ef, tcfg.grad_compression)
        gnorm = global_norm(grads)
        new_params, new_opt = opt.update(grads, state["opt"], params, state["step"])
        new_state = dict(
            state, params=new_params, opt=new_opt, step=state["step"] + 1
        )
        if new_ef is not None:
            new_state["ef"] = new_ef
        metrics = {"loss": loss, "aux_loss": aux, "grad_norm": gnorm}
        return new_state, metrics

    return train_step


def make_eval_step(cfg: ModelConfig) -> Callable:
    loss_fn = loss_fn_for(cfg)

    def eval_step(params, batch):
        loss, aux = loss_fn(params, batch)
        return {"loss": loss, "aux_loss": aux}

    return eval_step


# -- resnet (BatchNorm state threads through) --------------------------------


def init_resnet_train_state(cfg: ModelConfig, tcfg: TrainConfig, key) -> Dict[str, Any]:
    params, bn = resnet.init_resnet(key, cfg)
    opt = make_optimizer(tcfg)
    return {
        "params": params,
        "bn": bn,
        "opt": opt.init(params),
        "step": jnp.zeros((), jnp.int32),
    }


def make_resnet_train_step(cfg: ModelConfig, tcfg: TrainConfig) -> Callable:
    """The ResNet step.  Jitted, its program is ``jit_train_step`` and its
    operations sit under the ``train_step`` scope: the names by which a
    profiler trace's reader finds the step (``tests/test_loader_spans.py``
    pins them)."""
    opt = make_optimizer(tcfg)

    def train_step(state, batch):
        def loss_fn(p):
            loss, (new_bn, acc) = resnet.resnet_loss(p, state["bn"], batch, cfg, train=True)
            return loss, (new_bn, acc)

        with jax.named_scope("train_step"):
            (loss, (new_bn, acc)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                state["params"]
            )
            gnorm = global_norm(grads)
            new_params, new_opt = opt.update(grads, state["opt"], state["params"],
                                             state["step"])
            new_state = dict(
                state, params=new_params, bn=new_bn, opt=new_opt, step=state["step"] + 1
            )
        return new_state, {"loss": loss, "accuracy": acc, "grad_norm": gnorm}

    return train_step
