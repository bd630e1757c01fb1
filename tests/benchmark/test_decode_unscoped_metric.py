"""``decode_unscoped_ms_per_step`` (PR 36): the decode program's device
time under none of its scopes a launch, from what
``sparse_scopes.reduce`` already books as ``unscoped``; nothing to read
where there is no such time or no scope table."""

import pytest

from benchmark import common, run as bench_run, sparse_scopes

NAME = "decode_unscoped_ms_per_step"


@pytest.fixture(scope="module")
def read():
    return bench_run.load_module("layer_metrics", NAME).read


def test_the_manifest_lists_it_for_the_sparse_cell_alone():
    manifest = common.load_manifest()
    entry = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert entry == [{
        "name": NAME, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "serving engine",
        "moves": "serve_tokens_per_s",
        "workloads": ["keye-serve-docqa-32k"],
    }]
    assert manifest["per_layer"][-1] == entry[0]          # appended
    assert "serving engine" in {
        m["layer"] for m in manifest["per_layer"][:-1]
    }


def test_it_is_the_decode_programs_unscoped_time_a_launch(read):
    """Two decode launches: 40 + 30 ns of copies under no scope, 20 ns
    under ``index``, the while loop's envelope not counted; the chunk
    program's own copy (50 ns) is not this metric's."""
    dump = {"planes": {"/device:TPU:0": {
        "XLA Modules": [
            ["jit_step(1)", 0, 100, "", ""],
            ["jit_prefill(2)", 200, 100, "", ""],
            ["jit_step(1)", 400, 100, "", ""],
        ],
        "XLA Ops": [
            ["copy.1", 10, 40, "", "copy"],
            ["fusion.1", 60, 20, "", "fusion"],
            ["copy.1", 210, 50, "", "copy"],
            ["while.1", 400, 100, "", "while"],
            ["copy.1", 410, 30, "", "copy"],
        ],
    }}}
    tables = {
        "jit_step": {"copy.1": "jit(step)/copy",
                     "fusion.1": "jit(step)/attn/index/dot",
                     "while.1": "jit(step)/while"},
        "jit_prefill": {"copy.1": "jit(prefill)/copy"},
    }
    facts = {"sparse_scopes": sparse_scopes.reduce(
        sparse_scopes.label(dump, tables)
    )}
    assert facts["sparse_scopes"]["jit_step"]["scope_s"]["unscoped"] == \
        pytest.approx(70e-9)
    assert read(facts) == pytest.approx(1e3 * 70e-9 / 2)


@pytest.mark.parametrize("facts", [
    {"ctx": {}, "spans": [], "trace": None},
    {"sparse_scopes": None},
    {"sparse_scopes": {"jit_prefill": {
        "launches": 3, "scope_s": {"unscoped": 1.0}, "device_op_s": 2.0,
    }}},
    {"sparse_scopes": {"jit_step": {
        "launches": 0, "scope_s": {"unscoped": 1.0}, "device_op_s": 2.0,
    }}},
    {"sparse_scopes": {"jit_step": {
        "launches": 2, "scope_s": {"index": 1.0}, "device_op_s": 2.0,
    }}},
], ids=["a_dense_cells_run", "no_scopes", "chunk_only", "no_launch",
        "nothing_unscoped"])
def test_nothing_to_read_is_none_and_never_raises(read, facts):
    assert read(facts) is None
