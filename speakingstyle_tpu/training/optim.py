"""Optimizer: Adam + ramp-then-step-decay schedule, grad clip, accumulation.

Reference semantics (reference: model/optimizer.py:35-44): during the first
``loss.anneal_steps`` steps LR ramps linearly init_lr -> anneal_lr; after
that, LR = anneal_lr scaled by anneal_rate for every optimizer.anneal_steps
milestone passed. The lr for step s uses ``current_step = s + 1``
(step_and_update_lr increments before reading).

Built as an optax chain: clip_by_global_norm(1.0) -> adam(b1=0.9, b2=0.98,
eps=1e-9) -> schedule; grad accumulation via optax.MultiSteps.

``train.fused_optimizer`` swaps in ``make_fused_optimizer``: the same math
as one fused pass over a single raveled gradient vector. The hypothesis
was that the optax chain's ~200 leaves x 4 stages of per-leaf fusions
could be collapsed — but the end-to-end result was NEGATIVE: the
ravel/unravel copies cost more than the chain overhead they remove (read
on an earlier installation; no reading in PERF_LEDGER.jsonl, ROADMAP
C2). Off by default. Update parity with the chain is pinned by
tests/test_training.py::test_fused_optimizer_matches_chain.
"""

from typing import NamedTuple

import chex
import jax
import jax.flatten_util  # registers jax.flatten_util.ravel_pytree
import jax.numpy as jnp
import optax

from speakingstyle_tpu.configs.config import TrainConfig


def make_lr_schedule(train_cfg: TrainConfig):
    opt = train_cfg.optimizer
    ramp_steps = train_cfg.loss.anneal_steps
    init_lr = opt.init_lr
    anneal_lr = opt.anneal_lr
    milestones = jnp.asarray(opt.anneal_steps, jnp.float32)
    anneal_rate = opt.anneal_rate

    def schedule(step):
        current = jnp.asarray(step, jnp.float32) + 1.0
        ramp = init_lr + (current / ramp_steps) * (anneal_lr - init_lr)
        n_passed = jnp.sum(current > milestones)
        decayed = anneal_lr * jnp.power(anneal_rate, n_passed)
        return jnp.where(current > ramp_steps, decayed, ramp)

    return schedule


class FlatAdamState(NamedTuple):
    """Adam moments stored as single flat vectors (not per-leaf trees)."""

    count: chex.Array  # int32 scalar
    mu: chex.Array     # [n_params] f32
    nu: chex.Array     # [n_params] f32


def make_fused_optimizer(train_cfg: TrainConfig) -> optax.GradientTransformation:
    """clip_by_global_norm -> (L2 weight decay) -> Adam -> -lr, computed in
    one fused pass over the raveled gradient vector. Identical update math
    to the optax chain in make_optimizer (same stage order and the same
    step-count semantics: bias correction uses count+1, the schedule is
    evaluated at count)."""
    opt = train_cfg.optimizer
    schedule = make_lr_schedule(train_cfg)
    b1, b2 = opt.betas
    eps, clip, wd = opt.eps, opt.grad_clip_thresh, opt.weight_decay

    def init(params):
        flat, _ = jax.flatten_util.ravel_pytree(params)
        flat = flat.astype(jnp.float32)
        return FlatAdamState(
            count=jnp.zeros([], jnp.int32),
            mu=jnp.zeros_like(flat),
            nu=jnp.zeros_like(flat),
        )

    def update(grads, state, params=None):
        g, unravel = jax.flatten_util.ravel_pytree(grads)
        g = g.astype(jnp.float32)
        # optax.clip_by_global_norm: scale only when the norm exceeds clip
        gnorm = jnp.linalg.norm(g)
        g = g * jnp.where(gnorm < clip, 1.0, clip / gnorm)
        if wd:
            if params is None:
                raise ValueError("weight_decay needs params")
            p, _ = jax.flatten_util.ravel_pytree(params)
            g = g + wd * p.astype(jnp.float32)
        count_inc = state.count + 1
        mu = b1 * state.mu + (1.0 - b1) * g
        nu = b2 * state.nu + (1.0 - b2) * jnp.square(g)
        mu_hat = mu / (1.0 - b1 ** count_inc.astype(jnp.float32))
        nu_hat = nu / (1.0 - b2 ** count_inc.astype(jnp.float32))
        lr = schedule(state.count)
        upd = -lr * mu_hat / (jnp.sqrt(nu_hat) + eps)
        return unravel(upd), FlatAdamState(count=count_inc, mu=mu, nu=nu)

    tx = optax.GradientTransformation(init, update)
    if opt.grad_acc_step > 1:
        tx = optax.MultiSteps(tx, every_k_schedule=opt.grad_acc_step)
    return tx


class LeafAdamState(NamedTuple):
    """Adam moments as per-leaf trees (layout matches the param tree)."""

    count: chex.Array  # int32 scalar
    mu: chex.Array     # pytree like params
    nu: chex.Array     # pytree like params


class _Result:
    """Opaque (non-pytree) per-leaf carrier for (upd, mu, nu)."""

    __slots__ = ("upd", "mu", "nu")

    def __init__(self, upd, mu, nu):
        self.upd, self.mu, self.nu = upd, mu, nu


def make_leaf_fused_optimizer(train_cfg: TrainConfig) -> optax.GradientTransformation:
    """clip_by_global_norm -> (L2) -> Adam -> -lr with the whole chain
    written as ONE expression per leaf, so XLA emits ~one fused kernel per
    leaf instead of the optax chain's 4 stages x ~200 leaves with
    materialized intermediate update trees.

    This is the middle ground the r4 "flat" variant missed: no
    ravel/unravel copies (the flat impl's downfall), but also no
    per-stage HBM round trips. Update math is identical to the chain —
    pinned by tests/test_training.py::test_fused_optimizer_matches_chain —
    and the state layout (count + mu/nu trees) mirrors scale_by_adam's, so
    only the optax chain *wrapper* structure differs in checkpoints."""
    opt = train_cfg.optimizer
    schedule = make_lr_schedule(train_cfg)
    b1, b2 = opt.betas
    eps, clip, wd = opt.eps, opt.grad_clip_thresh, opt.weight_decay

    def init(params):
        return LeafAdamState(
            count=jnp.zeros([], jnp.int32),
            mu=jax.tree_util.tree_map(jnp.zeros_like, params),
            nu=jax.tree_util.tree_map(jnp.zeros_like, params),
        )

    def update(grads, state, params=None):
        if wd and params is None:
            raise ValueError("weight_decay needs params")
        # the one unavoidable extra pass: the global grad norm
        gnorm = jnp.sqrt(
            sum(
                jnp.sum(jnp.square(g.astype(jnp.float32)))
                for g in jax.tree_util.tree_leaves(grads)
            )
        )
        scale = jnp.where(gnorm < clip, 1.0, clip / gnorm)
        count_inc = state.count + 1
        c1 = 1.0 - b1 ** count_inc.astype(jnp.float32)
        c2 = 1.0 - b2 ** count_inc.astype(jnp.float32)
        lr = schedule(state.count)

        def leaf(g, mu, nu, p):
            g = g * scale
            if wd:
                g = g + wd * p
            mu2 = b1 * mu + (1.0 - b1) * g
            nu2 = b2 * nu + (1.0 - b2) * jnp.square(g)
            upd = -lr * (mu2 / c1) / (jnp.sqrt(nu2 / c2) + eps)
            # _Result is NOT a registered pytree, so tree_map treats it as
            # a leaf — unambiguous even if the param tree itself contains
            # tuple nodes (a plain 3-tuple here would collide with them)
            return _Result(upd, mu2, nu2)

        fused = jax.tree_util.tree_map(
            leaf, grads, state.mu, state.nu,
            params if params is not None else grads,
        )
        pick = lambda name: jax.tree_util.tree_map(
            lambda r: getattr(r, name), fused
        )
        return pick("upd"), LeafAdamState(
            count=count_inc, mu=pick("mu"), nu=pick("nu")
        )

    tx = optax.GradientTransformation(init, update)
    if opt.grad_acc_step > 1:
        tx = optax.MultiSteps(tx, every_k_schedule=opt.grad_acc_step)
    return tx


def make_optimizer(train_cfg: TrainConfig) -> optax.GradientTransformation:
    impl = train_cfg.fused_optimizer
    if impl not in (False, True, "flat", "leaf"):
        raise ValueError(
            f"fused_optimizer must be False|True|'flat'|'leaf', got {impl!r}"
        )
    if impl == "leaf":
        return make_leaf_fused_optimizer(train_cfg)
    if impl:  # True or "flat"
        return make_fused_optimizer(train_cfg)
    opt = train_cfg.optimizer
    tx = optax.chain(
        optax.clip_by_global_norm(opt.grad_clip_thresh),
        # torch.optim.Adam folds weight decay into the gradient BEFORE the
        # moment estimates (L2, not AdamW) — order matters for parity.
        optax.add_decayed_weights(opt.weight_decay) if opt.weight_decay else optax.identity(),
        optax.scale_by_adam(b1=opt.betas[0], b2=opt.betas[1], eps=opt.eps),
        optax.scale_by_learning_rate(make_lr_schedule(train_cfg)),
    )
    if opt.grad_acc_step > 1:
        tx = optax.MultiSteps(tx, every_k_schedule=opt.grad_acc_step)
    return tx
