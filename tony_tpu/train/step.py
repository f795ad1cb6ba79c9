"""Sharded training step factory for the flagship transformer.

The pjit recipe: resolve each param's logical axes against a rule table
(parallel/sharding.py), jit the step with those shardings, and let XLA insert
the collectives — gradient psum over data/fsdp, param all_gather +
grad reduce_scatter for fsdp, activation psum for tensor. The optimizer is
optax adamw; optimizer state inherits the param shardings (ZeRO-style: fsdp
shards optimizer moments for free).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import transformer
from ..parallel import sharding as shlib


@dataclass
class TrainStepBundle:
    step_fn: Callable          # (params, opt_state, tokens, targets) -> (params, opt_state, metrics)
    params: Any
    opt_state: Any
    mesh: Mesh
    rules: shlib.Rules
    config: transformer.TransformerConfig
    optimizer: optax.GradientTransformation
    param_shardings: Any = None
    opt_shardings: Any = None
    # the step's committed input sharding for [B, L] token/target arrays —
    # data loaders place batches with THIS (tony_tpu.data
    # device_put_sharded_batch(sharding=...)) so placement can't drift from
    # the jitted in_shardings
    tok_sharding: Any = None
    # jitted (params, tokens, targets) -> scalar loss with NO optimizer
    # update — the held-out evaluation path
    eval_fn: Any = None


def make_optimizer(
    lr: float = 3e-4, weight_decay: float = 0.01, grad_clip: float = 1.0
) -> optax.GradientTransformation:
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(lr, b1=0.9, b2=0.95, weight_decay=weight_decay),
    )


def create_train_step(
    cfg: transformer.TransformerConfig,
    mesh: Mesh,
    rules: shlib.Rules | None = None,
    key: jax.Array | None = None,
    optimizer: optax.GradientTransformation | None = None,
    use_ring_attention: bool | None = None,
    sp_impl: str | None = None,
) -> TrainStepBundle:
    """Initialize sharded params + optimizer state and build the jitted step.

    `sp_impl` picks the sequence-parallel attention when the mesh has a
    nontrivial `seq` axis: "ring" (K/V ppermute ring) or "ulysses" (all-to-all
    head sharding). Defaults to "ring"; `use_ring_attention` is the older
    boolean form of the same switch.

    Checkpointing contract: the jitted step DONATES params/opt_state
    (donate_argnums), so the previous step's buffers are dead the moment
    the next step dispatches — checkpoint through
    ``CheckpointManager.save_async`` (train/checkpoint.py), which
    snapshots to host synchronously before overlapping the write, never
    by handing live device arrays to a background saver.
    """
    if cfg.n_latent_layers:
        raise ValueError(
            "'latent' layers cannot be trained here: a head's q and k are "
            "wider than its v, which the flash kernels (forward and "
            "backward) do not take; the kind is served only")
    rules = dict(rules if rules is not None else shlib.FSDP_TP_RULES)
    if sp_impl is None:
        want_sp = (
            use_ring_attention
            if use_ring_attention is not None
            else mesh.shape.get("seq", 1) > 1
        )
        sp_impl = "ring" if want_sp else None
    if sp_impl is not None and sp_impl not in ("ring", "ulysses"):
        raise ValueError(f"unknown sp_impl {sp_impl!r}")
    if sp_impl:
        cfg = transformer.TransformerConfig(
            **{**cfg.__dict__, "attn_impl": sp_impl}
        )
        rules.setdefault("act_seq", "seq")
    key = jax.random.PRNGKey(0) if key is None else key
    optimizer = optimizer or make_optimizer()

    axes_tree = transformer.param_logical_axes(cfg)
    param_shardings = shlib.tree_shardings(mesh, axes_tree, rules)

    init_fn = jax.jit(
        functools.partial(transformer.init, cfg=cfg), out_shardings=param_shardings
    )
    params = init_fn(key)
    opt_shardings = _opt_state_shardings(
        jax.eval_shape(optimizer.init, params), params, param_shardings, mesh
    )
    opt_state = jax.jit(optimizer.init, out_shardings=opt_shardings)(params)

    seq_axis = rules.get("act_seq") if sp_impl else None
    tok_sharding = NamedSharding(mesh, P(rules.get("batch"), seq_axis))

    def step(params, opt_state, tokens, targets):
        loss, grads = jax.value_and_grad(transformer.loss_fn)(
            params, tokens, targets, cfg, mesh, rules
        )
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        gnorm = optax.global_norm(grads)
        return params, opt_state, {"loss": loss, "grad_norm": gnorm}

    step_fn = jax.jit(
        step,
        in_shardings=(param_shardings, opt_shardings, tok_sharding, tok_sharding),
        out_shardings=(param_shardings, opt_shardings, None),
        donate_argnums=(0, 1),
    )
    def eval_loss(params, tokens, targets):
        return transformer.loss_fn(params, tokens, targets, cfg, mesh, rules)

    eval_fn = jax.jit(
        eval_loss,
        in_shardings=(param_shardings, tok_sharding, tok_sharding),
    )

    bundle = TrainStepBundle(
        step_fn=step_fn, params=params, opt_state=opt_state, mesh=mesh,
        rules=rules, config=cfg, optimizer=optimizer,
    )
    bundle.param_shardings = param_shardings
    bundle.opt_shardings = opt_shardings
    bundle.tok_sharding = tok_sharding
    bundle.eval_fn = eval_fn
    return bundle


def _opt_state_shardings(opt_state_shape, params, param_shardings, mesh):
    """Shardings for an optax state: subtrees that mirror the param tree
    (adam mu/nu etc.) take the param shardings — FSDP shards optimizer
    moments ZeRO-style — and everything else (step counts) is replicated."""
    params_treedef = jax.tree.structure(params)
    replicated = NamedSharding(mesh, P())

    def rec(node):
        if jax.tree.structure(node) == params_treedef and not isinstance(
            node, jax.ShapeDtypeStruct
        ):
            return param_shardings
        if isinstance(node, tuple) and hasattr(node, "_fields"):  # NamedTuple
            return type(node)(*(rec(c) for c in node))
        if isinstance(node, (tuple, list)):
            return type(node)(rec(c) for c in node)
        if isinstance(node, dict):
            return {k: rec(v) for k, v in node.items()}
        return replicated

    return rec(opt_state_shape)


def make_forward(
    cfg: transformer.TransformerConfig, mesh: Mesh | None = None
) -> Callable:
    """Jitted inference forward (logits only)."""

    @jax.jit
    def fwd(params, tokens):
        logits, _ = transformer.apply(params, tokens, cfg, mesh)
        return logits

    return fwd


def synthetic_lm_batch(key, batch: int, seq: int, vocab: int):
    """Next-token-predictable synthetic stream (affine sequences mod vocab)."""
    k1, k2 = jax.random.split(key)
    start = jax.random.randint(k1, (batch, 1), 0, vocab)
    step_ = jax.random.randint(k2, (batch, 1), 1, 7)
    pos = jnp.arange(seq + 1)[None, :]
    toks = (start + step_ * pos) % vocab
    return toks[:, :-1].astype(jnp.int32), toks[:, 1:].astype(jnp.int32)
