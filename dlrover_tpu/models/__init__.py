"""Model zoo: functional JAX models with logical-axis sharding metadata.

Every model exposes ``init_params(config, rng) -> (params, logical_axes)``
and ``forward(config, params, tokens, ...) -> (logits, aux)`` as pure
functions — no framework Module state, so checkpointing, resharding, and
pipelining operate on plain pytrees.
"""

from dlrover_tpu.models.llama import (  # noqa: F401
    TpuLMConfig,
    init_params,
    forward,
    loss_fn,
)


def model_for(config):
    """The module that implements ``config``'s kind of model: its
    ``init_params`` / ``param_axes`` / ``loss_fn`` (and, where the model
    has state that is not trained, ``init_buffers`` / ``buffer_axes``)."""
    import importlib

    return importlib.import_module(f"dlrover_tpu.models.{config.kind}")
