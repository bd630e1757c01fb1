"""``sparse_chunk_attn_ms_per_chunk`` (PR 34): the prefill program's
``attn/sparse`` device time a chunk launch, from what
``sparse_scopes.reduce`` already gathers; nothing to read where the
program has no such scope."""

import pytest

from benchmark import common, run as bench_run, sparse_scopes

NAME = "sparse_chunk_attn_ms_per_chunk"
KERNEL = "paged_pool_sparse_chunk_attention.1"


@pytest.fixture(scope="module")
def read():
    return bench_run.load_module("layer_metrics", NAME).read


def test_the_manifest_lists_it_for_the_sparse_cell_alone():
    entry = [
        m for m in common.load_manifest()["per_layer"] if m["name"] == NAME
    ]
    assert entry == [{
        "name": NAME, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "serve_tokens_per_s",
        "workloads": ["keye-serve-docqa-32k"],
    }]


def test_it_is_the_chunk_programs_sparse_scope_a_launch(read):
    """Two chunk launches with 30 + 50 ns under ``sparse`` (a kernel and
    a fusion beside it) and 20 ns under ``experts``; the decode step's
    own ``sparse`` time is not this metric's."""
    dump = {"planes": {"/device:TPU:0": {
        "XLA Modules": [
            ["jit_step(1)", 0, 100, "", ""],
            ["jit_prefill(2)", 200, 100, "", ""],
            ["jit_prefill(2)", 400, 100, "", ""],
        ],
        "XLA Ops": [
            ["fusion.1", 10, 70, "", "fusion"],
            [KERNEL, 210, 30, "", "custom-call"],
            ["fusion.2", 250, 20, "", "fusion"],
            [KERNEL, 410, 40, "", "custom-call"],
            ["fusion.3", 460, 10, "", "fusion"],
        ],
    }}}
    tables = {
        "jit_step": {"fusion.1": "jit(step)/attn/sparse/gather"},
        "jit_prefill": {
            KERNEL: "jit(prefill)/attn/sparse/pallas_call",
            "fusion.2": "jit(prefill)/mlp/experts/gmm",
            "fusion.3": "jit(prefill)/attn/sparse/transpose",
        },
    }
    facts = {"sparse_scopes": sparse_scopes.reduce(
        sparse_scopes.label(dump, tables)
    )}
    assert read(facts) == pytest.approx(1e3 * 80e-9 / 2)


@pytest.mark.parametrize("facts", [
    {"ctx": {}, "spans": [], "trace": None},
    {"sparse_scopes": None},
    {"sparse_scopes": {"jit_step": {
        "launches": 3, "scope_s": {"sparse": 1.0}, "device_op_s": 2.0,
    }}},
    {"sparse_scopes": {"jit_prefill": {
        "launches": 0, "scope_s": {"sparse": 1.0}, "device_op_s": 2.0,
    }}},
    {"sparse_scopes": {"jit_prefill": {
        "launches": 2, "scope_s": {"experts": 1.0}, "device_op_s": 2.0,
    }}},
], ids=["a_parents_run", "no_scopes", "decode_only", "no_launch",
        "no_sparse_scope"])
def test_nothing_to_read_is_none_and_never_raises(read, facts):
    assert read(facts) is None
