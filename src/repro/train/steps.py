"""Train / serve step functions -- the units the dry-run lowers and the
training loop jits.

``train_step``       : standard pjit path (GSPMD inserts the gradient
                       collectives implied by the param shardings).
``serve_prefill``    : prompt processing -> logits + decode cache.
``serve_step``       : one decode token against a KV/state cache.
``train_step_compressed`` : DP via shard_map with WORp-sketch gradient
                       all-reduce + error feedback (paper application); model
                       axes stay on pjit-style replication inside the shard.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import model as M
from repro.optim import adamw, gradcomp


class TrainState(NamedTuple):
    params: Any
    opt: adamw.AdamWState


def train_step(state: TrainState, batch, cfg: ArchConfig, lr: float = 3e-4,
               wedge: bool = False):
    """Loss + grads + AdamW update (pjit/GSPMD path)."""
    def loss_fn(p):
        return M.train_loss(p, batch, cfg, wedge=wedge)

    loss, grads = jax.value_and_grad(loss_fn)(state.params)
    new_params, new_opt = adamw.update(state.params, grads, state.opt, lr=lr)
    return TrainState(params=new_params, opt=new_opt), {"loss": loss}


def serve_prefill(params, batch, cfg: ArchConfig, wedge: bool = False):
    return M.prefill(params, batch, cfg, wedge=wedge)


def serve_step(params, batch, cfg: ArchConfig):
    return M.decode_step(params, batch, cfg)


# ---------------------------------------------------------------------------
# WORp-compressed data parallelism
# ---------------------------------------------------------------------------

class CompressedTrainState(NamedTuple):
    params: Any
    opt: adamw.AdamWState
    error: Any  # worker-local error-feedback tree (f32)


def make_compressed_train_step(cfg: ArchConfig, mesh,
                               cc: gradcomp.CompressorConfig,
                               dp_axes: Sequence[str] = ("data",),
                               lr: float = 3e-4):
    """Build a shard_map'd DP train step with WORp gradient compression.

    Params/opt/error are REPLICATED over the dp axes (pure DP; appropriate
    for the small/medium archs this feature targets -- see DESIGN.md); the
    batch is sharded.  The only gradient collective is the sketch psum (+ the
    2k-float pass-II all-reduce), instead of an N-float dense all-reduce.
    """
    def local_step(params, opt, error, batch):
        def loss_fn(p):
            return M.train_loss(p, batch, cfg)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        loss = jax.lax.pmean(loss, dp_axes)
        sparse, new_err, stats = gradcomp.tree_compress_step(
            grads, error, cc, dp_axes)
        new_params, new_opt = adamw.update(params, sparse, opt, lr=lr)
        return new_params, new_opt, new_err, {"loss": loss, **stats}

    rep = P()
    batch_spec = {"tokens": P(dp_axes), "labels": P(dp_axes)}
    fn = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(rep, rep, rep, batch_spec),
        out_specs=(rep, rep, rep, rep),
        check_vma=False,
    )

    def step(state: CompressedTrainState, batch):
        p, o, e, metrics = fn(state.params, state.opt, state.error, batch)
        return CompressedTrainState(params=p, opt=o, error=e), metrics

    return step


def make_compressed_train_step_tp(cfg: ArchConfig, mesh,
                                  cc: gradcomp.CompressorConfig,
                                  dp_axes: Sequence[str] = ("data",),
                                  lr: float = 3e-4):
    """WORp-compressed DP x TP train step (full-scale hillclimb variant).

    shard_map is MANUAL over the dp axes only (``axis_names``); the model
    axis stays auto, so params/opt/EF remain TP-sharded inside.  Per-worker
    error feedback is stacked on a leading dp axis.  The gradient collective
    is the sketch psum + pass-II value psum instead of the dense all-reduce.
    """
    def local_step(params, opt, error, batch):
        error = jax.tree_util.tree_map(lambda e: e[0], error)

        def loss_fn(p):
            return M.train_loss(p, batch, cfg)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        loss = jax.lax.pmean(loss, dp_axes)
        sparse, new_err, stats = gradcomp.tree_compress_step_sharded(
            grads, error, cc, dp_axes)
        new_params, new_opt = adamw.update(params, sparse, opt, lr=lr)
        new_err = jax.tree_util.tree_map(lambda e: e[None], new_err)
        return new_params, new_opt, new_err, {"loss": loss, **stats}

    rep = P()
    dp = tuple(dp_axes)
    err_spec = P(dp)
    batch_spec = {"tokens": P(dp), "labels": P(dp)}
    fn = jax.shard_map(
        local_step, mesh=mesh, axis_names=set(dp_axes),
        in_specs=(rep, rep, err_spec, batch_spec),
        out_specs=(rep, rep, err_spec, rep),
        check_vma=False,
    )

    def step(state: CompressedTrainState, batch):
        p, o, e, metrics = fn(state.params, state.opt, state.error, batch)
        return CompressedTrainState(params=p, opt=o, error=e), metrics

    return step
