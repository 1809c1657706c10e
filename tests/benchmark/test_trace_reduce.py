"""The reduction from trace events to numbers: on events made by hand, where
every answer is known, and on a small trace recorded on the chip."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.trace import reduce as tr  # noqa: E402

US = 1000  # events are in nanoseconds


def by_hand():
    """One device, a window of 1,000 us. Program ``jit_step`` runs 100-500:
    a ``while`` (100-400) over two operations (100-250, 300-400) and one
    more operation (450-500), so 400-450 is a gap inside it;
    ``jit_prefill`` runs 600-700; 700-1000 is idle while the host sleeps,
    and 0-100 while it dispatches."""
    ops = [["while.1", 100 * US, 300 * US], ["fusion.1", 100 * US, 150 * US],
           ["fusion.2", 300 * US, 100 * US], ["fusion.3", 450 * US, 50 * US],
           ["fusion.1", 600 * US, 100 * US],
           ["fusion.9", 1100 * US, 50 * US]]  # after the window: clipped away
    modules = [["jit_step(123)", 100 * US, 400 * US],
               ["jit_prefill(456)", 600 * US, 100 * US]]
    host = [["python", tr.WINDOW_MARK, 0, 1000 * US],
            ["python", "$engine.py:766 step", 0, 720 * US],
            ["python", "$dispatch", 10 * US, 80 * US],
            ["python", "$array.py:631 _value", 390 * US, 70 * US],
            ["python", "$time sleep", 720 * US, 280 * US]]
    return {"devices": [{"name": "/device:TPU:0", "ops": ops,
                         "modules": modules}], "host": host}


def test_busy_union_counts_a_loop_once_and_whole():
    s = tr.reduce_events(by_hand())
    assert s["window_s"] == pytest.approx(1e-3)
    # the while's 300 (its body's events cover 250 of them), 50 and 100
    assert s["busy_s"] == pytest.approx(450e-6)
    assert s["busy_s_fullest"] == s["busy_s"] and s["devices"] == 1


def test_device_time_by_operation_takes_the_leaves():
    s = tr.reduce_events(by_hand())
    assert s["by_op"] == {"jit_step/fusion.1": pytest.approx(150e-6),
                          "jit_step/fusion.2": pytest.approx(100e-6),
                          "jit_step/fusion.3": pytest.approx(50e-6),
                          "jit_prefill/fusion.1": pytest.approx(100e-6)}
    assert s["by_program"] == {"jit_step": pytest.approx(400e-6),
                               "jit_prefill": pytest.approx(100e-6)}
    assert s["program_calls"] == {"jit_step": 1, "jit_prefill": 1}
    assert tr.breakdown(s)["device_ops"][0] == [
        "jit_step/fusion.1", pytest.approx(150e-6)]


def test_gaps_go_to_the_innermost_host_frame():
    gaps = tr.reduce_events(by_hand())["idle_gaps"]
    assert gaps == {"$dispatch": pytest.approx(100e-6),
                    "$array.py:631 _value": pytest.approx(50e-6),
                    "$engine.py:766 step": pytest.approx(100e-6),
                    "$time sleep": pytest.approx(300e-6)}


def test_scopes_of_two_programs_are_kept_apart():
    """A window that runs two programs: both number their instructions
    alike, so a scope is looked up by program and instruction; two texts of
    one program that disagree keep the path they share."""
    def text(program, *lines):
        return f"HloModule {program}, is_scheduled=true\n" + "".join(
            f'  %{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p), '
            f'metadata={{op_name="{scope}"}}\n' for name, scope in lines)

    scopes = tr.scopes_by_program([
        text("jit_step", ("fusion.1", "jit(step)/while/body/lm_moe/dot")),
        text("jit_prefill", ("fusion.1", "jit(prefill)/lm_attn/dot_general"),
             ("fusion.2", "jit(prefill)/lm_moe/dot")),
        text("jit_prefill", ("fusion.1", "jit(prefill)/lm_attn/add"),
             ("fusion.2", "jit(prefill)/lm_loss/dot"))])
    assert scopes == {"jit_step/fusion.1": "jit(step)/while/body/lm_moe/dot",
                      "jit_prefill/fusion.1": "jit(prefill)/lm_attn",
                      "jit_prefill/fusion.2": "jit(prefill)"}
    s = tr.reduce_events(by_hand(), scopes)
    # fusion.1 ran 150 us in jit_step and 100 us in jit_prefill
    assert tr.scope_seconds(s, "lm_moe") == pytest.approx(150e-6)
    assert tr.scope_seconds(s, "lm_attn") == pytest.approx(100e-6)
    assert tr.breakdown(s)["device_ops"][0] == [
        "jit_step/fusion.1 <while/body/lm_moe/dot>", pytest.approx(150e-6)]
    assert ["jit_step/fusion.2", pytest.approx(100e-6)] in \
        tr.breakdown(s)["device_ops"]


def test_scopes_come_from_the_compiled_text():
    hlo = ('  %fusion.2 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, '
           'metadata={op_name="jit(step)/blockwise_q_block_0/dot_general"}\n'
           '  ROOT %fusion.1 = f32[8]{0} fusion(f32[8]{0} %q), '
           'metadata={op_name="jit(step)/moe/dot_general"}\n')
    scopes = tr.scopes_from_hlo(hlo)
    assert scopes["fusion.2"].endswith("blockwise_q_block_0/dot_general")
    s = tr.reduce_events(by_hand(), scopes)
    assert tr.scope_seconds(s, "blockwise_q_block_") == pytest.approx(100e-6)
    # fusion.2 runs inside while.1, which is outside the scope: none exposed
    assert tr.scope_seconds(s, "blockwise_q_block_", exposed=True) == \
        pytest.approx(0.0)
    assert tr.scope_seconds(s, "moe/", exposed=True) == pytest.approx(100e-6)
    assert tr.scope_seconds(s, "no_such_scope") == 0.0


def test_short_name_and_program_name():
    assert tr.short_name("%copy.116 = bf16[6,40]{1,0} copy(bf16[6,40] %x)") \
        == "copy.116"
    assert tr.program_of("jit_step(12241519639451953319)") == "jit_step"


def test_a_trace_without_device_work_is_refused():
    events = by_hand()
    events["devices"][0]["ops"] = []
    with pytest.raises(ValueError):
        tr.reduce_events(events)


RECORDED = os.path.join(os.path.dirname(tr.__file__),
                        "recorded_v5e_serve.json")


def test_recorded_chip_trace_reduces_to_its_known_numbers():
    """Half a second of ``serve-chat-steady`` on a TPU v5 lite (PR 24):
    the numbers were read once from this file and are pinned here."""
    with open(RECORDED) as f:
        events = json.load(f)
    s = tr.reduce_events(events)
    known = json.load(open(RECORDED.replace(".json", ".known.json")))
    assert s["window_s"] == pytest.approx(known["window_s"])
    assert s["busy_s"] == pytest.approx(known["busy_s"])
    assert s["program_calls"] == known["program_calls"]
    for name, sec in known["by_program"].items():
        assert s["by_program"][name] == pytest.approx(sec)
    assert tr.breakdown(s)["device_ops"][0][0] == known["top_op"]
    assert 0.0 < s["busy_s"] <= s["window_s"]
    assert sum(s["idle_gaps"].values()) == pytest.approx(
        s["window_s"] - s["busy_s"], rel=0.05)
