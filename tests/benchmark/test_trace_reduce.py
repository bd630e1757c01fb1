"""``benchmark/trace_reduce.py`` on a recorded trace and on made-up
ones. ``data/train_2steps_v5e.json`` is the event dump of the first two
traced steps of ``mistral7b-train`` on a TPU v5e (PR 23's first chip
run), as ``dump_xplane`` writes it; the numbers below are what the
reduction made of it then, so a change to the arithmetic shows."""

import json
import os

import pytest

from benchmark import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "train_2steps_v5e.json")) as f:
        return json.load(f)


def test_recorded_trace_busy_and_idle(recorded):
    out = tr.reduce(recorded)
    assert out["n_planes"] == 1
    assert out["window_s"] == pytest.approx(0.500557659, rel=1e-9)
    assert out["busy_s"] == pytest.approx(0.49490032, rel=1e-9)
    assert out["idle_share"] == pytest.approx(0.0113020726, rel=1e-6)
    assert out["module_s"] == {"jit_step": pytest.approx(0.494923513)}


def test_recorded_trace_scopes_and_kernels(recorded):
    out = tr.reduce(recorded)
    assert out["scope_s"] == {
        "mlp": pytest.approx(0.211897168),
        "attn": pytest.approx(0.099056532),
        "vocab": pytest.approx(0.083521302),
        "optimizer": pytest.approx(0.057944307),
        "unscoped": pytest.approx(0.042460828),
    }
    assert out["unscoped_share"] == pytest.approx(0.0858002268, rel=1e-6)
    # fwd, dq and dk/dv of two layers in two steps: twelve calls.
    assert out["kernel_s"] == {"attn": pytest.approx(0.04498535)}
    top = dict(out["device_ops"])
    assert list(top)[:2] == [
        "mlp:fusion", "mlp:bitcast_dynamic-update-slice_fusion"
    ]
    assert top["attn:attn"] == pytest.approx(0.04498535)
    assert len(out["device_ops"]) == 10


def test_recorded_trace_gaps_are_labelled_by_the_host_span(recorded):
    gaps = dict(tr.reduce(recorded)["idle_gaps"])
    # The device waits while the host fetches the loss and builds the
    # next batch; the step call itself overlaps device work.
    assert gaps["bench.loss_fetch"] == pytest.approx(0.005270786)
    assert gaps["bench.batch_build"] == pytest.approx(0.000386539)
    assert sum(gaps.values()) == pytest.approx(
        0.500557659 - 0.49490032, rel=1e-6
    )


def test_envelopes_are_not_counted_twice():
    dump = {"host": [], "planes": {"/device:TPU:0": {"XLA Ops": [
        ["while.1", 0, 1000, "jit(f)/while", "while"],
        ["fusion.1", 0, 400, "jit(f)/while/body/mlp/dot", "fusion"],
        ["fusion.2", 500, 500, "jit(f)/transpose(jvp(vocab))/dot", "fusion"],
    ]}}}
    out = tr.reduce(dump)
    assert out["busy_s"] == pytest.approx(1e-6)        # the union
    assert out["scope_s"] == {
        "mlp": pytest.approx(4e-7), "vocab": pytest.approx(5e-7),
    }
    assert out["unscoped_share"] == 0.0


def test_idle_gap_goes_to_the_innermost_host_span():
    ops = [["fusion.1", 0, 100, "", "fusion"],
           ["fusion.2", 600, 100, "", "fusion"],
           ["fusion.3", 900, 100, "", "fusion"]]
    host = [["bench.engine_step", 0, 1000], ["bench.decode", 90, 520]]
    out = tr.reduce(
        {"host": host, "planes": {"/device:TPU:0": {"XLA Ops": ops}}}
    )
    assert out["window_s"] == pytest.approx(1e-6)
    assert dict(out["idle_gaps"]) == {
        "bench.decode": pytest.approx(5e-7),
        "bench.engine_step": pytest.approx(2e-7),
    }
    assert out["idle_share"] == pytest.approx(0.7)


def test_two_programs_name_their_unscoped_ops():
    dump = {"host": [], "planes": {"/device:TPU:0": {
        "XLA Modules": [["jit_prefill(123)", 0, 300],
                        ["jit_step(456)", 400, 600]],
        "XLA Ops": [["fusion.1", 0, 300, "", "fusion"],
                    ["fusion.2", 400, 600, "", "fusion"]],
    }}}
    out = tr.reduce(dump)
    assert dict(out["device_ops"]) == {
        "jit_step:fusion": pytest.approx(6e-7),
        "jit_prefill:fusion": pytest.approx(3e-7),
    }
    assert out["module_s"] == {
        "jit_prefill": pytest.approx(3e-7), "jit_step": pytest.approx(6e-7),
    }


def test_two_chips_average():
    plane = {"XLA Ops": [["fusion.1", 0, 500, "", "fusion"]]}
    other = {"XLA Ops": [["fusion.1", 0, 1000, "", "fusion"]]}
    out = tr.reduce({"host": [], "planes": {"a": plane, "b": other}})
    assert out["n_planes"] == 2
    assert out["busy_s"] == pytest.approx(7.5e-7)


def test_nothing_on_the_device_is_nothing():
    assert tr.reduce({"host": [["bench.step_call", 0, 10]],
                      "planes": {}}) is None


HLO = """
  %fusion.390 = f32[2,4096]{1,0:T(2,128)S(1)} fusion(%gte.1), kind=kLoop, calls=%fc.142, metadata={op_name="jit(step)/jvp()/while/body/closed_call/attn/reduce" stack_frame_id=3}
  ROOT %attn.30 = (bf16[2,4096,4096]{2,1,0}) custom-call(%copy.278), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp()/while/body/attn/pallas_call"}
  %while.10 = (s32[]{:T(128)}, bf16[2]{0}) while(%tuple.155), condition=%c, body=%b, metadata={op_name="jit(step)/jvp()/while"}
"""


def test_scopes_and_categories_come_from_hlo_text():
    scopes = tr.scopes_from_hlo(HLO)
    assert scopes["fusion.390"].endswith("/attn/reduce")
    assert tr.scope_of(scopes["attn.30"]) == "attn"
    assert tr.scope_of(scopes["while.10"]) == "unscoped"
    assert tr.scope_of("jit(step)/transpose(jvp(vocab))/dot") == "vocab"
    assert tr.scope_of("jit(step)/vocabulary/dot") == "unscoped"
    lines = [line.strip() for line in HLO.strip().splitlines()]
    assert tr.parse_event_name(lines[0]) == ("fusion.390", "fusion")
    assert tr.parse_event_name(lines[1]) == (
        "attn.30", "custom-call:tpu_custom_call"
    )
    assert tr.parse_event_name(lines[2]) == ("while.10", "while")
    assert tr.base_name("fusion.390") == "fusion"
    assert tr.base_name("attn.30") == "attn"


def test_crop_keeps_whole_events_only(recorded):
    half = tr.crop(recorded, 250_000_000)
    ops = half["planes"]["/device:TPU:0"]["XLA Ops"]
    assert 0 < len(ops) < len(recorded["planes"]["/device:TPU:0"]["XLA Ops"])
    assert len(half["host"]) < len(recorded["host"])
