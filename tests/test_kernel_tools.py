"""The kernel tools that no other test rehearses (``bench_paged_decode.py``
and ``bench_moe_dispatch.py`` have theirs beside what they time): each
runs ``--tiny`` on a CPU to its end and ends with a line of JSON, so a
chip call is not where a wrong import or argument is found."""

import json
import os
import subprocess
import sys

import pytest

TOOLS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"
)


@pytest.mark.parametrize("tool", [
    "bench_kda_scan.py", "bench_flash_blocks.py", "bench_sparse_attention.py",
    "bench_sparse_attention.py --parts block_select",
    "bench_kda_layer.py",
])
def test_a_kernel_tool_rehearses_off_a_tpu(tool):
    tool, *args = tool.split()
    out = subprocess.run(
        [sys.executable, os.path.join(TOOLS, tool), "--tiny", *args],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, text=True,
        capture_output=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert isinstance(json.loads(out.stdout.splitlines()[-1]), dict)


def test_trace_query_splits_a_scopes_device_ops_by_op(tmp_path):
    """``tools/trace_query.py --device-ops``: the ops of a traced run's
    dump that ran inside a launch of the program under the named scope,
    by (kind, path below the scope), a launch."""
    dump = {"planes": {"/device:TPU:0": {
        "XLA Modules": [
            ["jit_step(1)", 100, 50], ["jit_prefill(2)", 200, 50],
            ["jit_step(1)", 300, 50],
        ],
        "XLA Ops": [
            ["fusion.1", 101, 4_000_000, "jit(step)/attn/select/gather",
             "fusion"],
            ["sort.1", 110, 2_000_000, "jit(step)/attn/select/vmap()/top_k",
             "sort"],
            ["fusion.2", 120, 9_000_000, "jit(step)/attn/sparse/gather",
             "fusion"],
            ["fusion.3", 210, 9_000_000, "jit(prefill)/attn/select/gather",
             "fusion"],
            ["fusion.1", 301, 2_000_000, "jit(step)/attn/select/gather",
             "fusion"],
        ],
    }}, "host": []}
    path = tmp_path / "trace_dump.json"
    path.write_text(json.dumps(dump))
    out = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "trace_query.py"),
         "--device-ops", "select", "--program", "jit_step", "--json",
         str(path)],
        text=True, capture_output=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    table = json.loads(out.stdout)
    assert table["launches"] == 2
    assert table["ms_per_launch"] == pytest.approx(4.0)
    assert table["rows"] == [
        {"kind": "fusion", "under": "gather", "ms_per_launch": 3.0,
         "ops_per_launch": 1.0},
        {"kind": "sort", "under": "vmap()/top_k", "ms_per_launch": 1.0,
         "ops_per_launch": 0.5},
    ]
