"""Fully-fused training pipeline: sample + gather + forward/backward in
ONE compiled program.

The reference's hot loop crosses the host every batch: python drives
sampler kernels, then a feature gather, then the torch step
(``examples/pyg/ogbn_products_sage_quiver.py:138-147``).  On TPU the whole
chain is expressible as a single jit — seeds in, (state, loss) out — so
steady-state training has zero host round-trips and XLA overlaps sampling
gathers with the previous layer's compute.  Requires the feature hot tier
to cover the graph (HBM-resident or ici-sharded); budgeted hot/cold setups
fall back to the two-stage loop (``SeedLoader``).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import optax

from .feature import Feature, _lookup_tables
from .sampler import GraphSageSampler, run_pipeline
from .parallel.train import Frontier, TrainState, call_model
from . import telemetry
from .telemetry.device_scopes import (HOST_STEP_EPOCH, HOST_STEP_EVAL,
                                      HOST_STEP_TRAIN, MODEL, OPTIMIZER,
                                      register_program)

__all__ = ["make_fused_train_step", "make_fused_eval_fn"]


def _check(feature: Feature):
    assert feature.cache_count >= feature.node_count, (
        "fused pipeline needs the feature fully HBM-resident "
        f"(cache {feature.cache_count} < nodes {feature.node_count}); "
        "use SeedLoader for budgeted hot/cold configs"
    )


def make_fused_train_step(sampler: GraphSageSampler, feature: Feature,
                          apply_fn: Callable,
                          tx: optax.GradientTransformation,
                          loss_fn: Optional[Callable] = None):
    """Build ``(state, seeds, labels, label_mask, key) -> (state, loss)``
    with sampling and feature gather inside the jit.

    ``apply_fn(params, x, blocks, train=, rngs=)`` is a model over rows
    and blocks alone.  One that also has a ``frontier`` parameter (a typed
    model, a model with batch statistics) is handed the sampled frontier
    and ``state.model_state`` and returns ``(logits, model_state)``:
    :func:`quiver_tpu.parallel.train.call_model`.

    Each call of the jitted program (``jit_qt_fused_train_step``) folds
    into the ``step.train`` span, as ``make_scan_epoch``'s does into
    ``step.epoch`` and ``make_fused_eval_fn``'s into ``step.eval``: the
    call until it returns to Python, which is how long the launch holds
    the caller's thread and says nothing of the device."""
    impl = _fused_train_impl(sampler, feature, apply_fn, loss_fn)
    tables = _tables(sampler, feature)
    jitted = jax.jit(impl, donate_argnums=(1,))
    registered = False

    def step(state: TrainState, seeds, labels, label_mask, key):
        nonlocal registered
        args = (tables, state, seeds, labels, label_mask, key)
        if not registered:      # before the call: ``state`` is donated
            registered = True
            register_program(jitted, args)
        # how long the launch holds the caller's thread, not the device
        with telemetry.span(HOST_STEP_TRAIN):
            return jitted(*args)

    return step


def _tables(sampler: GraphSageSampler, feature: Feature):
    """The device tables a fused program reads — ``(indptr, indices,
    (hot, order))``, and a typed graph's relation windows after them
    (``GraphSageSampler.windows_device``) — handed to it as ARGUMENTS: a
    device array captured by a jitted closure is baked into the executable
    as a constant, a second copy of graph and features in HBM for every
    program."""
    indptr, indices = sampler.csr_topo.to_device(sampler.device)
    tables = (indptr, indices, feature._device_tables())
    if getattr(sampler, "schema", None) is not None:
        tables += (sampler.windows_device(),)
    return tables


def _fused_train_impl(sampler: GraphSageSampler, feature: Feature,
                      apply_fn: Callable, loss_fn: Optional[Callable]):
    """Un-jitted ``(tables, state, seeds, labels, label_mask, key) ->
    (state, loss)`` shared by the fused step and the scan epoch.

    The three jitted programs of this file carry names of their own
    (``jit_qt_fused_train_step``, ``jit_qt_scan_epoch``,
    ``jit_qt_fused_eval``): a trace's ``XLA Modules`` line names a program
    by its function, and the persistent compile cache keys on that name but
    NOT on scope names (debug information is stripped from the key), so a
    program whose ``qt.*`` scopes change must change its name too or
    ``telemetry.device_scopes`` reads the old scopes back from the cache."""
    _check(feature)
    sizes = tuple(sampler.sizes)
    gm, srng = sampler.gather_mode, sampler.sample_rng
    dedup = sampler.dedup
    caps = tuple(sampler.frontier_caps)
    schema = getattr(sampler, "schema", None)

    if loss_fn is None:
        def loss_fn(logits, labels, mask):
            ls = optax.softmax_cross_entropy_with_integer_labels(
                logits, labels
            )
            m = mask.astype(ls.dtype)
            return (ls * m).sum() / jnp.maximum(m.sum(), 1.0)

    def qt_fused_train_step(tables, state: TrainState, seeds, labels,
                            label_mask, key):
        indptr, indices, feat_tables = tables[:3]
        ks, kd = jax.random.split(key)
        n_id, n_mask, num, blocks, _, _ = run_pipeline(
            dedup, indptr, indices, seeds, ks, sizes, caps, gather_mode=gm,
            sample_rng=srng, schema=schema,
            windows=None if schema is None else tables[3]
        )
        x = _lookup_tables(feat_tables, n_id, n_mask)

        def compute(params):
            with jax.named_scope(MODEL):
                logits, model_state = call_model(
                    apply_fn, params, x, blocks, Frontier(n_id, n_mask),
                    state.model_state, True, {"dropout": kd})
                return loss_fn(logits, labels, label_mask), model_state

        (loss, model_state), grads = jax.value_and_grad(
            compute, has_aux=True)(state.params)
        with jax.named_scope(OPTIMIZER):
            updates, opt_state = state.tx.update(grads, state.opt_state,
                                                 state.params)
            params = optax.apply_updates(state.params, updates)
        return TrainState(params, opt_state, state.tx, model_state), loss

    return qt_fused_train_step


def make_scan_epoch(sampler: GraphSageSampler, feature: Feature,
                    apply_fn: Callable, tx: optax.GradientTransformation,
                    loss_fn: Optional[Callable] = None):
    """Whole-epoch ``lax.scan`` variant of the fused step.

    ``(state, seeds [S, B], labels [S, B], key) -> (state, losses [S])`` —
    S steps execute as ONE device program: no per-step dispatch at all.
    Compile cost is paid once per (S, B) shape; use for steady production
    epochs, the plain fused step for interactive work.
    """
    step = _fused_train_impl(sampler, feature, apply_fn, loss_fn)
    tables = _tables(sampler, feature)

    @jax.jit
    def qt_scan_epoch(tables, state: TrainState, seeds, labels, key):
        S, B = seeds.shape
        ones = jnp.ones((B,), bool)

        def body(state, xs):
            s, l, k = xs
            return step(tables, state, s, l, ones, k)

        keys = jax.random.split(key, S)
        state, losses = jax.lax.scan(body, state, (seeds, labels, keys))
        return state, losses

    registered = False

    def epoch(state: TrainState, seeds, labels, key):
        nonlocal registered
        args = (tables, state, seeds, labels, key)
        if not registered:
            registered = True
            register_program(qt_scan_epoch, args)
        with telemetry.span(HOST_STEP_EPOCH):
            return qt_scan_epoch(*args)

    return epoch


def make_fused_eval_fn(sampler: GraphSageSampler, feature: Feature,
                       apply_fn: Callable):
    """``(params, seeds, key, model_state=None) -> logits`` with sampling
    inside the jit; ``model_state`` is for an ``apply_fn`` that asks for
    the frontier (``call_model``) and is read, never written."""
    _check(feature)
    tables = _tables(sampler, feature)
    sizes = tuple(sampler.sizes)
    gm, srng = sampler.gather_mode, sampler.sample_rng

    dedup = sampler.dedup
    caps = tuple(sampler.frontier_caps)
    schema = getattr(sampler, "schema", None)

    @jax.jit
    def qt_fused_eval(tables, params, seeds, key, model_state):
        indptr, indices, feat_tables = tables[:3]
        n_id, n_mask, num, blocks, _, _ = run_pipeline(
            dedup, indptr, indices, seeds, key, sizes, caps, gather_mode=gm,
            sample_rng=srng, schema=schema,
            windows=None if schema is None else tables[3]
        )
        x = _lookup_tables(feat_tables, n_id, n_mask)
        with jax.named_scope(MODEL):
            logits, _ = call_model(apply_fn, params, x, blocks,
                                   Frontier(n_id, n_mask), model_state,
                                   False, None)
            return logits

    registered = False

    def eval_fn(params, seeds, key, model_state=None):
        nonlocal registered
        args = (tables, params, seeds, key,
                {} if model_state is None else model_state)
        if not registered:
            registered = True
            register_program(qt_fused_eval, args)
        with telemetry.span(HOST_STEP_EVAL):
            return qt_fused_eval(*args)

    return eval_fn
