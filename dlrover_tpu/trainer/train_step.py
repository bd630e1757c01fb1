"""Sharded training step factory.

Builds a jitted train step whose state (params + optimizer moments) is
laid out by the logical-axis rules (parallel/sharding.py) over a
(dp, ep, pp, sp, tp) mesh: FSDP via embed-dim sharding, TP via heads/mlp/
vocab, EP via expert dims; pipeline via trainer/pipeline.py. Optimizer
moments inherit the param shardings (ZeRO), the step counter is
replicated. Gradient accumulation runs as a ``lax.scan`` so the global
batch is fixed regardless of data-parallel size — the JAX analogue of the
reference's ``ElasticTrainer`` fixed-batch grad-accum
(trainer/torch/elastic/trainer.py:53-86).
"""

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlrover_tpu.models import model_for
from dlrover_tpu.parallel.sharding import (
    DEFAULT_RULES,
    logical_to_spec,
    sharding_tree,
    spec_tree,
)


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 3e-4
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    grad_clip: float = 1.0
    warmup_steps: int = 100
    grad_accum: int = 1              # microbatches per step (fixed batch)


def make_optimizer(tc: TrainConfig) -> optax.GradientTransformation:
    schedule = optax.warmup_cosine_decay_schedule(
        init_value=0.0,
        peak_value=tc.learning_rate,
        warmup_steps=max(tc.warmup_steps, 1),
        decay_steps=100_000,
        end_value=tc.learning_rate * 0.1,
    )
    return optax.chain(
        optax.clip_by_global_norm(tc.grad_clip),
        optax.adamw(
            schedule,
            b1=tc.beta1,
            b2=tc.beta2,
            weight_decay=tc.weight_decay,
        ),
    )


# ---------------------------------------------------------------------------
# State & sharding layout
# ---------------------------------------------------------------------------


def _has_buffers(model) -> bool:
    return hasattr(model, "init_buffers")


def _buffers_kw(model, buffers):
    """``loss_fn``'s extra argument for a model that has buffers."""
    return {"buffers": buffers} if _has_buffers(model) else {}


def state_specs(
    config,
    optimizer: optax.GradientTransformation,
    rules=DEFAULT_RULES,
) -> Dict[str, Any]:
    """PartitionSpec pytree for {"params", "opt_state", "step"} — and
    "buffers" where the config's model (``models.model_for``) has state
    that is not trained: beside the parameters, outside the optimizer."""
    model = model_for(config)
    pshapes = jax.eval_shape(
        lambda: model.init_params(config, jax.random.key(0))[0]
    )
    param_specs = spec_tree(model.param_axes(config), rules)
    opt_shapes = jax.eval_shape(optimizer.init, pshapes)
    opt_specs = optax.tree_map_params(
        optimizer,
        lambda _, s: s,
        opt_shapes,
        param_specs,
        transform_non_params=lambda _: P(),
        is_leaf=lambda x: isinstance(x, P),
    )
    specs = {"params": param_specs, "opt_state": opt_specs, "step": P()}
    if _has_buffers(model):
        specs["buffers"] = spec_tree(model.buffer_axes(config), rules)
    return specs


def state_shardings(specs, mesh: Mesh):
    return sharding_tree(specs, mesh)


def batch_spec(rules=DEFAULT_RULES) -> P:
    # tokens [batch, seq+1]: batch over (dp, ep); seq left unsharded at
    # input (activations get re-sharded onto sp by constraint).
    return logical_to_spec(("batch", None), rules)


def init_train_state(
    config,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    rng: jax.Array,
    rules=DEFAULT_RULES,
):
    """Initialize params+opt sharded directly on the mesh (no host blowup)."""
    specs = state_specs(config, optimizer, rules)
    shardings = state_shardings(specs, mesh)

    model = model_for(config)

    def init(rng):
        params, _ = model.init_params(config, rng)
        state = {
            "params": params,
            "opt_state": optimizer.init(params),
            "step": jnp.zeros((), jnp.int32),
        }
        if _has_buffers(model):
            state["buffers"] = model.init_buffers(config, rng)
        return state

    with mesh:
        state = jax.jit(init, out_shardings=shardings)(rng)
    return state, specs


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------


def make_train_step(
    config,
    tc: TrainConfig,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    rules=DEFAULT_RULES,
    loss_fn: Optional[Callable] = None,
    donate: bool = True,
):
    """Returns jitted ``step(state, batch) -> (state, metrics)``.

    batch["tokens"]: [grad_accum * micro_batch, seq+1] int32. The leading
    dim is split into ``grad_accum`` scan iterations; gradients average in
    f32. ``metrics`` carries the model's own counters (the ``counters``
    of its loss's aux, summed over the microbatches) beside the loss,
    and, for a model whose loss has a second part (``ce_mtp``: a
    multi-token-prediction module), the two parts ``ce`` and ``ce_mtp``
    apart, averaged over the microbatches.
    """
    model = model_for(config)
    attention_fn = None
    if dict(mesh.shape).get("sp", 1) > 1:
        # Sequence-parallel mesh: attention must hop K/V around the sp
        # ring (plain attention over a seq-sharded constraint would make
        # XLA all-gather the full sequence on every layer).
        from dlrover_tpu.ops.ring_attention import make_ring_attention

        attention_fn = make_ring_attention(mesh, rules)

    def _loss(params, batch, buffers):
        if loss_fn is not None:
            return loss_fn(params, batch)
        return model.loss_fn(
            config, params, batch, attention_fn=attention_fn,
            **_buffers_kw(model, buffers),
        )

    specs = state_specs(config, optimizer, rules)
    shardings = state_shardings(specs, mesh)
    bspec = NamedSharding(mesh, batch_spec(rules))

    def single_grad(params, micro, buffers):
        (loss, aux), grads = jax.value_and_grad(_loss, has_aux=True)(
            params, micro, buffers
        )
        # A loss of two parts reports them apart; a loss of one part adds
        # nothing (its program stays as it was).
        parts = ("ce", "ce_mtp") if "ce_mtp" in aux else ()
        parts = {k: aux[k] for k in parts}
        return loss, (aux.get("counters", {}), parts), grads

    def step(state, batch):
        params = state["params"]
        buffers = state.get("buffers")
        tokens = batch["tokens"]
        ga = tc.grad_accum
        if ga > 1:
            if tokens.shape[0] % ga:
                raise ValueError(
                    f"batch {tokens.shape[0]} not divisible by "
                    f"grad_accum {ga}"
                )
            mb = tokens.shape[0] // ga
            micro_tokens = tokens.reshape(ga, mb, tokens.shape[-1])

            def accum(carry, mt):
                loss, counters, grads = single_grad(
                    params, {"tokens": mt}, buffers
                )
                g_acc, l_acc = carry
                g_acc = jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(jnp.float32) / ga,
                    g_acc,
                    grads,
                )
                return (g_acc, l_acc + loss / ga), counters

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            (grads, loss), (counters, parts) = jax.lax.scan(
                accum, (zeros, jnp.zeros((), jnp.float32)), micro_tokens
            )
            counters = {k: jnp.sum(v, axis=0) for k, v in counters.items()}
            parts = {k: jnp.mean(v, axis=0) for k, v in parts.items()}
        else:
            loss, (counters, parts), grads = single_grad(
                params, {"tokens": tokens}, buffers
            )

        # named_scope: lands in the compiled HLO's op_name, which
        # benchmark/trace_reduce.py buckets device time by.
        with jax.named_scope("optimizer"):
            updates, new_opt = optimizer.update(
                grads, state["opt_state"], params
            )
            new_params = optax.apply_updates(params, updates)
            grad_norm = optax.global_norm(grads)
        new_state = dict(
            state,
            params=new_params,
            opt_state=new_opt,
            step=state["step"] + 1,
        )
        metrics = {
            "loss": loss,
            "grad_norm": grad_norm,
            "step": new_state["step"],
            **counters,
            **parts,
        }
        return new_state, metrics

    jitted = jax.jit(
        step,
        in_shardings=(shardings, {"tokens": bspec}),
        out_shardings=(shardings, None),
        donate_argnums=(0,) if donate else (),
    )

    def run(state, batch):
        # Trace (first call) must happen inside the mesh context so the
        # logical sharding constraints in the model resolve.
        with mesh:
            return jitted(state, batch)

    # The raw jit object, for AOT compilation (``run.jitted.lower(
    # abstract_state, abstract_batch).compile()``) — restart paths
    # overlap the compile with the restore H2D.
    run.jitted = jitted
    return run, specs


def make_eval_step(config, mesh, rules=DEFAULT_RULES):
    bspec = NamedSharding(mesh, batch_spec(rules))

    model = model_for(config)

    def ev(params, batch, buffers):
        loss, metrics = model.loss_fn(
            config, params, batch, **_buffers_kw(model, buffers)
        )
        return metrics["ce"]

    jitted = jax.jit(ev, in_shardings=(None, {"tokens": bspec}, None))

    def run(params, batch, buffers=None):
        """``buffers``: the state's, for a model that has them."""
        with mesh:  # trace inside the mesh so logical constraints apply
            return jitted(params, batch, buffers)

    return run
