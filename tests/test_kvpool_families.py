"""The one seam between the paged engine and the model families
(``kvpool/families.py``, docs/DESIGN.md "A served family"): every family
module states the protocol, ``kv_stats()`` of a tiny engine of each reads
as it did before the seam was drawn (the table below was written from the
parent's output, commit 3ffb3bf), and the arrows between the package's
files point one way.
"""

import ast
import os

import jax
import pytest

from dlrover_tpu.models import (
    conv_lm,
    latent_lm,
    linear_sparse_lm,
    llama,
    model_for,
    sparse_lm,
    window_lm,
)
from dlrover_tpu.serving import kvpool
from dlrover_tpu.serving.kvpool import PagedServingEngine

pytestmark = pytest.mark.kvpool


def _delta_config():
    from benchmark.runners import serve_delta
    from tests.benchmark import tiny_olmo_hybrid

    return serve_delta.delta_config(tiny_olmo_hybrid.CONFIG)


# family -> (the tiny config and engine its serving tests build, the
# family's module, what kv_stats() said of its programs at the parent,
# and the keys that family's engine alone reports).
_ALWAYS = {
    "free", "used", "cached", "bytes_in_use", "cow_copies", "pool_attention",
    "kv_layers", "state_layers", "engine_build_s", "warmup_s", "total",
    "min_ref", "negative_refs", "prefix_entries", "prefix_evicted_blocks",
    "prefix_hit_blocks", "prefix_hit_rate", "prefix_hit_tokens",
    "prefix_hits", "prefix_misses",
}
_STATE = {
    "state_bytes", "state_snapshots_live", "state_snapshot_bytes",
    "state_snapshot_capacity", "state_restores",
    "state_restores_from_snapshot", "state_snapshots",
    "state_snapshots_denied", "state_snapshots_given_up",
    "prefix_rounded_down_blocks", "moe_rows_dropped",
}
FAMILIES = {
    "llama": dict(
        config=llama.tiny_config, module="dense",
        engine=dict(slots=3, max_len=64, prefill_chunk=8, block_size=4),
        kinds={"pool_attention": "xla_gather"}, keys=set(),
    ),
    "sparse_lm": dict(
        config=sparse_lm.tiny_config, module="sparse",
        engine=dict(slots=3, max_len=96, prefill_chunk=8, block_size=4),
        kinds={"pool_attention": "sparse_gather",
               "sparse_chunk_attention": "masked_attention"},
        keys={"index_bytes_in_use", "index_pool_bytes",
              "index_tokens_per_row", "moe_rows_dropped"},
    ),
    "latent_lm": dict(
        config=latent_lm.tiny_config, module="latent",
        engine=dict(slots=3, max_len=64, prefill_chunk=8, block_size=4,
                    num_blocks=60),
        kinds={"pool_attention": "latent_absorbed",
               "latent_decode_attention": "gathered_view",
               "latent_chunk_attention": "absorbed"},
        keys={"latent_bytes_in_use", "latent_pool_bytes", "latent_row_bytes",
              "latent_chunk_query_rows", "moe_rows_dropped"},
    ),
    "conv_lm": dict(
        config=conv_lm.tiny_config, module="conv",
        engine=dict(slots=3, max_len=64, prefill_chunk=8, block_size=4,
                    num_blocks=60),
        kinds={"pool_attention": "conv_gathered_view",
               "conv_decode_attention": "gathered_view",
               "conv_chunk_attention": "gathered_view"},
        keys=_STATE | {"conv_chunk_rows_launched", "conv_chunk_rows_scored"},
    ),
    "window_lm": dict(
        config=window_lm.tiny_config, module="window",
        engine=dict(slots=3, max_len=160, prefill_chunk=16, block_size=8,
                    num_blocks=64, window_blocks=28),
        kinds={"pool_attention": "window_groups",
               "window_decode_attention": "gathered_view",
               "window_chunk_attention": "gathered_view"},
        keys={"groups", "window_rows", "window_blocks_released_total",
              "prefix_rounded_down_blocks", "moe_rows_dropped",
              "prefix_tails_live", "prefix_tails_dropped"},
    ),
    "linear_sparse_lm": dict(
        config=linear_sparse_lm.tiny_config, module="linear",
        engine=dict(slots=3, max_len=128, prefill_chunk=16, block_size=8,
                    num_blocks=80),
        kinds={"pool_attention": "linear_block_lists",
               "lightning_chunk": "jnp", "lightning_decode": "jnp",
               "block_select": "jnp",
               "block_decode_attention": "gathered_pages",
               "block_chunk_attention": "masked_blocks"},
        keys=_STATE | {"ckey_bytes_in_use", "ckey_bytes", "ckey_copy_groups",
                       "ckey_copy_groups_run_share"},
    ),
    "delta_lm": dict(
        config=_delta_config, module="delta",
        engine=dict(slots=3, max_len=192, prefill_chunk=16, block_size=8,
                    num_blocks=100),
        kinds={"pool_attention": "delta_state_and_pages",
               "delta_chunk": "jnp", "delta_decode": "jnp",
               "full_decode_attention": "gathered_view",
               "full_chunk_attention": "gathered_view"},
        keys=_STATE | {"state_array_bytes"},
    ),
}


@pytest.fixture(scope="module")
def engines():
    """One tiny engine a family, built once (no program is traced: a
    ``kv_stats()`` reads the host's books alone)."""
    out = {}
    for kind, spec in FAMILIES.items():
        cfg = spec["config"]()
        assert cfg.kind == kind
        init = model_for(cfg).init_params
        if kind == "llama":     # (its tree comes with its axes' names)
            params = init(cfg, jax.random.key(0))[0]
        else:
            params = jax.jit(lambda key, c=cfg, f=init: f(c, key))(
                jax.random.key(0)
            )
        out[kind] = PagedServingEngine(cfg, params, **spec["engine"])
    return out


@pytest.mark.parametrize("kind", list(FAMILIES))
def test_kv_stats_reads_as_it_did_at_the_parent(engines, kind):
    spec, stats = FAMILIES[kind], engines[kind].kv_stats()
    assert set(stats) == _ALWAYS | set(spec["kinds"]) | spec["keys"]
    assert {name: stats[name] for name in spec["kinds"]} == spec["kinds"]
    assert engines[kind].pool_attention == spec["kinds"]["pool_attention"]


def _package_files():
    root = os.path.dirname(kvpool.__file__)
    for name in sorted(os.listdir(root)):
        if name.endswith(".py"):
            with open(os.path.join(root, name)) as f:
                yield name, f.read()


def _imports(tree):
    """Every module a tree imports, at module level or inside a function:
    ``from a.b import c`` counts as ``a.b`` and ``a.b.c``."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name
        elif isinstance(node, ast.ImportFrom):
            yield node.module or ""
            for alias in node.names:
                yield f"{node.module}.{alias.name}"


@pytest.mark.parametrize("kind", list(FAMILIES))
def test_the_family_module_states_the_protocol(engines, kind):
    from dlrover_tpu.serving.kvpool import families

    eng, module = engines[kind], families.programs_for(engines[kind].config)
    assert module.__name__.rsplit(".", 1)[1] == FAMILIES[kind]["module"]
    assert eng._family is module
    for name in ("kinds", "build_decode", "build_prefill"):
        assert callable(getattr(module, name)), name
    assert isinstance(module.POOL_ATTENTION, str)
    kinds = module.kinds(
        eng.config, eng.config.compute_dtype, eng.block_size,
        eng.prefill_chunk, eng.slots, eng.max_blocks,
    )
    assert kinds and all(
        isinstance(k, str) and isinstance(v, str) for k, v in kinds.items()
    )
    # ... which is what the engine's programs were keyed by and built with
    assert eng.kinds == kinds and eng._steps.kinds == tuple(sorted(
        kinds.items()
    ))
    counts = {"decode": 0, "prefill": 0}
    assert callable(module.build_decode(
        eng.config, eng.slots, eng.max_blocks, eng.block_size, counts, kinds
    ))
    assert callable(module.build_prefill(
        eng.config, eng.max_blocks, eng.block_size, eng.prefill_chunk,
        counts, kinds,
    ))
    assert counts == {"decode": 0, "prefill": 0}    # (nothing traced)


def test_the_arrows_point_one_way():
    """No file of the package but ``__init__.py`` imports the engine, and
    the engine imports no family module, at module level or inside a
    function: it finds one through ``families.programs_for`` alone."""
    package = "dlrover_tpu.serving.kvpool"
    modules = {
        package + "." + spec["module"] for spec in FAMILIES.values()
    }
    seen = 0
    for name, text in _package_files():
        imported = set(_imports(ast.parse(text)))
        if name != "__init__.py":
            assert package + ".engine" not in imported, name
        if name == "engine.py":
            assert not imported & modules, sorted(imported & modules)
            assert "Imported here" not in text
            for gone in ("_GroupedSteps", "_LinearSteps", "_is_linear",
                         "_is_delta"):
                assert gone not in text, gone
        seen += 1
    assert seen >= 15
    from dlrover_tpu.models import generate

    assert not hasattr(generate, "NESTED_TREE_KINDS")

