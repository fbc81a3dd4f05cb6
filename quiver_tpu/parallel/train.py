"""Data-parallel training utilities.

Reference parity: the reference leaves the training loop to user PyG code
with ``DistributedDataParallel`` (e.g. ``examples/multi_gpu/pyg/
ogb-products/dist_sampling_ogb_products_quiver.py:82-160``).  We provide the
TPU-idiomatic equivalent so examples stay 3-line swaps: a jitted train step
whose batch is sharded over the mesh's data axis and whose gradients are
averaged by XLA (``NamedSharding`` on inputs does what DDP's NCCL allreduce
did — no wrapper class needed).
"""

from __future__ import annotations

import inspect
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import telemetry
from ..telemetry.device_scopes import (HOST_STEP_TRAIN, MODEL, OPTIMIZER,
                                       register_program)

__all__ = ["TrainState", "Frontier", "call_model", "make_train_step",
           "shard_batch", "replicate"]


class Frontier(NamedTuple):
    """The sampled frontier a model's input rows were gathered for: node
    ids and validity of ``x``'s rows (``SampledBatch.n_id`` /
    ``n_id_mask``).  A typed model derives node types from the ids and
    keeps padded rows out of its batch statistics."""

    n_id: jax.Array     # [P] int32
    mask: jax.Array     # [P] bool


def wants_frontier(apply_fn: Callable) -> bool:
    """Whether ``apply_fn`` asks for more than ``x`` and ``blocks``: it
    does by having a ``frontier`` parameter."""
    try:
        return "frontier" in inspect.signature(apply_fn).parameters
    except (TypeError, ValueError):     # a callable with no signature
        return False


def call_model(apply_fn: Callable, params, x, blocks, frontier, model_state,
               train: bool, rngs):
    """``(logits, model_state)`` of one model call, by what ``apply_fn``
    asks for.  One with a ``frontier`` parameter is called
    ``apply_fn(params, x, blocks, train=, rngs=, frontier=Frontier(...),
    model_state=...)`` and returns ``(logits, new model_state)``; any other
    (GraphSAGE, GAT, GCN) is called ``apply_fn(params, x, blocks, train=,
    rngs=)`` as ever and its state passes through untouched."""
    if wants_frontier(apply_fn):
        return apply_fn(params, x, blocks, train=train, rngs=rngs,
                        frontier=frontier, model_state=model_state)
    return apply_fn(params, x, blocks, train=train, rngs=rngs), model_state


class TrainState:
    """Minimal train state (params + opt state), pytree-registered.

    ``model_state`` holds what a model carries from step to step that is
    no parameter (BatchNorm's running averages: flax's ``batch_stats``).
    For a model without any it is ``{}``, a pytree with no leaf, so a
    program over such a state takes the arguments it always took."""

    def __init__(self, params, opt_state, tx, model_state=None):
        self.params = params
        self.opt_state = opt_state
        self.tx = tx
        self.model_state = {} if model_state is None else model_state

    def tree_flatten(self):
        return (self.params, self.opt_state, self.model_state), self.tx

    @classmethod
    def tree_unflatten(cls, tx, children):
        return cls(children[0], children[1], tx, children[2])

    @classmethod
    def create(cls, params, tx, model_state=None):
        return cls(params, tx.init(params), tx, model_state)


jax.tree_util.register_pytree_node(
    TrainState, TrainState.tree_flatten, TrainState.tree_unflatten
)


def make_train_step(apply_fn: Callable, tx: optax.GradientTransformation,
                    loss_fn: Optional[Callable] = None,
                    mesh: Optional[Mesh] = None, data_axis: str = "data"):
    """Build a jitted ``(state, x, blocks, labels, label_mask, key,
    frontier=None) -> (state, loss)`` step.

    With ``mesh`` given, inputs are expected sharded over ``data_axis``
    (leading dim); params replicated.  XLA inserts the gradient psum —
    the DDP equivalent.

    ``frontier`` (a :class:`Frontier`: the batch's ``n_id`` and
    ``n_id_mask``) is for an ``apply_fn`` that asks for it
    (:func:`call_model`); such a model's state travels in
    ``state.model_state``.  On a mesh the replicas' states are averaged,
    and each call of the jitted program (``jit_qt_dp_train_step``) folds
    into the ``step.train`` span: the call until it returns to Python, the
    caller's thread and not the device.
    """
    if loss_fn is None:
        def loss_fn(logits, labels, mask):
            ls = optax.softmax_cross_entropy_with_integer_labels(
                logits, labels
            )
            m = mask.astype(ls.dtype)
            return (ls * m).sum() / jnp.maximum(m.sum(), 1.0)

    def apply_and_loss(params, model_state, x, blocks, labels, label_mask,
                       key, frontier):
        logits, model_state = call_model(
            apply_fn, params, x, blocks, frontier, model_state, True,
            {"dropout": key})
        return loss_fn(logits, labels, label_mask), model_state

    def step(state: TrainState, x, blocks, labels, label_mask, key,
             frontier=None):
        (loss, model_state), grads = jax.value_and_grad(
            apply_and_loss, has_aux=True)(
            state.params, state.model_state, x, blocks, labels, label_mask,
            key, frontier)
        updates, opt_state = state.tx.update(grads, state.opt_state,
                                             state.params)
        params = optax.apply_updates(state.params, updates)
        return TrainState(params, opt_state, state.tx, model_state), loss

    if mesh is None:
        # donate the state: params/opt_state buffers update in place on
        # device instead of being copied every step
        return jax.jit(step, donate_argnums=(0,))

    # Data-parallel variant: the batch pytree is STACKED on a leading
    # replica axis of size mesh.shape[data_axis] (each replica sampled its
    # own seeds, so frontiers are per-replica — the GNN analogue of DDP's
    # per-rank batch).  vmap over the replica axis + sharded inputs makes
    # XLA place one replica per device and psum the gradients.
    ndev = int(mesh.shape[data_axis])

    # the program's name (``jit_qt_dp_train_step``) keys the device
    # scopes and the compile cache, as ``pipeline.py``'s programs' do
    def qt_dp_train_step(state: TrainState, x, blocks, labels, label_mask,
                         key, frontier=None):
        keys = jax.random.split(key, ndev)

        def compute(params):
            with jax.named_scope(MODEL):
                losses, model_states = jax.vmap(
                    lambda xx, bb, ll, mm, kk, ff: apply_and_loss(
                        params, state.model_state, xx, bb, ll, mm, kk, ff
                    )
                )(x, blocks, labels, label_mask, keys, frontier)
                return losses.mean(), jax.tree_util.tree_map(
                    lambda a: a.mean(axis=0), model_states)

        (loss, model_state), grads = jax.value_and_grad(
            compute, has_aux=True)(state.params)
        with jax.named_scope(OPTIMIZER):
            updates, opt_state = state.tx.update(grads, state.opt_state,
                                                 state.params)
            params = optax.apply_updates(state.params, updates)
        return TrainState(params, opt_state, state.tx, model_state), loss

    repl = NamedSharding(mesh, P())
    data = NamedSharding(mesh, P(data_axis))
    jitted = jax.jit(
        qt_dp_train_step,
        donate_argnums=(0,),
        in_shardings=(repl, data, data, data, data, repl, data),
        out_shardings=(repl, repl),
    )
    registered = False

    # one sharding per argument: the frontier is always handed over
    def sharded_step(state, x, blocks, labels, label_mask, key,
                     frontier=None):
        nonlocal registered
        args = (state, x, blocks, labels, label_mask, key, frontier)
        if not registered:      # before the call: ``state`` is donated
            registered = True
            register_program(jitted, args)
        # how long the launch holds the caller's thread, not the device
        with telemetry.span(HOST_STEP_TRAIN):
            return jitted(*args)

    sharded_step.jitted = jitted    # to lower it for a described mesh
    return sharded_step


def shard_batch(mesh: Mesh, tree, data_axis: str = "data"):
    """Put a host batch onto the mesh, sharded on the leading dim."""
    sh = NamedSharding(mesh, P(data_axis))
    return jax.tree_util.tree_map(lambda a: jax.device_put(a, sh), tree)


def replicate(mesh: Mesh, tree):
    sh = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(lambda a: jax.device_put(a, sh), tree)
