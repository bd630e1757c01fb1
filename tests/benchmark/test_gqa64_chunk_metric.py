"""``gqa64_chunk_attn_ms_per_chunk`` (PR 50): the prefill program's
``attn/gqa`` device time a chunk launch of a convolution / attention
pattern model, from what ``conv_scopes.reduce`` already gathers: the
chunk kernel over the flat pool where the program has it, the gathered
prefix's fusions and copies where it has not (a parent's run), and
nothing where the program has no such scope."""

import pytest

from benchmark import common, conv_scopes, run as bench_run, sparse_scopes

NAME = "gqa64_chunk_attn_ms_per_chunk"
KERNEL = "paged_flat_chunk_attention.1"
MODULES = [
    ["jit_step(1)", 0, 100, "", ""],
    ["jit_prefill(2)", 200, 100, "", ""],
    ["jit_prefill(2)", 400, 100, "", ""],
]


@pytest.fixture(scope="module")
def read():
    return bench_run.load_module("layer_metrics", NAME).read


def _facts(ops, tables):
    dump = {"host": [], "planes": {"/device:TPU:0": {
        "XLA Modules": MODULES, "XLA Ops": ops,
    }}}
    return {"sparse_scopes": conv_scopes.reduce(
        sparse_scopes.label(dump, tables)
    )}


def test_the_manifest_lists_it_for_the_conv_cell_alone():
    """One entry, an addition: beside the decode step's reader, with its
    unit, direction, source, layer and end-to-end metric."""
    per_layer = common.load_manifest()["per_layer"]
    entry = [m for m in per_layer if m["name"] == NAME]
    assert entry == [{
        "name": NAME, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "serve_tokens_per_s",
        "workloads": ["lfm2-serve-sessions-8k"],
    }]
    (step,) = [m for m in per_layer if m["name"] == "gqa64_attn_ms_per_step"]
    assert {k: v for k, v in step.items() if k != "name"} == {
        k: v for k, v in entry[0].items() if k != "name"
    }


@pytest.mark.parametrize("program", ["pool_kernel", "gathered_view"])
def test_it_is_the_chunk_programs_gqa_scope_a_launch(read, program):
    """Two chunk launches; under ``attn/gqa`` the kernel and the
    projections beside it (30 + 40 + 10 ns), or a parent's gathered
    prefix: its copy and its softmax's fusions (30 + 50 + 20 ns);
    ``mlp/experts`` and the decode step's own ``gqa`` time are not this
    metric's."""
    if program == "pool_kernel":
        ops = [
            [KERNEL, 210, 30, "", "custom-call"],
            [KERNEL, 410, 40, "", "custom-call"],
            ["fusion.3", 460, 10, "", "fusion"],
        ]
        table = {KERNEL: "jit(prefill)/attn/gqa/pallas_call",
                 "fusion.3": "jit(prefill)/attn/gqa/dot_general"}
        want = 80e-9
    else:
        ops = [
            ["copy.1", 210, 30, "", "copy"],
            ["fusion.3", 410, 50, "", "fusion"],
            ["fusion.4", 470, 20, "", "fusion"],
        ]
        table = {"copy.1": "jit(prefill)/attn/gqa/while/body/gather",
                 "fusion.3": "jit(prefill)/attn/gqa/while/body/exp",
                 "fusion.4": "jit(prefill)/attn/gqa/dot_general"}
        want = 100e-9
    facts = _facts(
        [["fusion.1", 10, 70, "", "fusion"],
         ["fusion.2", 250, 20, "", "fusion"]] + ops,
        {"jit_step": {"fusion.1": "jit(step)/attn/gqa/pallas_call"},
         "jit_prefill": dict(
             table, **{"fusion.2": "jit(prefill)/mlp/experts/gmm"}
         )},
    )
    assert read(facts) == pytest.approx(1e3 * want / 2)
    # the decode step's reader takes the other program's scope
    step = bench_run.load_module("layer_metrics", "gqa64_attn_ms_per_step")
    assert step.read(facts) == pytest.approx(1e3 * 70e-9)


@pytest.mark.parametrize("facts", [
    {"ctx": {}, "spans": [], "trace": None},        # a run with no dump
    {"sparse_scopes": None},
    {"sparse_scopes": {"jit_prefill": {"launches": 0, "scope_s": {}}}},
    # a chunk program of another model: no ``gqa`` scope
    {"sparse_scopes": {"jit_prefill": {
        "launches": 2, "scope_s": {"mla": 1e-3, "experts": 2e-3},
    }}},
])
def test_a_program_without_the_scope_gives_nothing_to_read(read, facts):
    assert read(facts) is None
