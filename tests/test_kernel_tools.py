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
])
def test_a_kernel_tool_rehearses_off_a_tpu(tool):
    out = subprocess.run(
        [sys.executable, os.path.join(TOOLS, tool), "--tiny"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, text=True,
        capture_output=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert isinstance(json.loads(out.stdout.splitlines()[-1]), dict)
