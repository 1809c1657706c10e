"""The readers of the fenced dispatch's two halves (``tick.dispatch``,
``tick.fence``) and of the garbage collector's pauses (``host.gc``), each on
a ``run`` made by hand where every answer is known, and their entries in
``BENCHMARK.json``. All three read the host's clock over the whole window:
the device may run a program during its enqueue, so host time under the
two halves does not split the device's idle."""

import gc
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import registry  # noqa: E402
from deeplearning4j_tpu.telemetry import trace as tr  # noqa: E402

T0 = 1000.0  # the window opens here on perf_counter


def serve_run(ring, fenced=True) -> dict:
    """A window of 10 s whose last 4 s are traced (6 to 10). Five ticks of
    2 s: a prefill of 0.4 s in the ticks that admit (0, 2 and 3), its
    enqueue 0.05 s and its fence 0.34 s; the decode phase 1.0 s, its
    enqueue 0.05 s and its fence 0.9 s. In the traced part (ticks 3 and 4)
    the decode program ran 0.8 s a tick and the prefill program 0.3 s, on
    a window the device's clock measures as 4 s. The garbage collector
    stopped the process 0.2 s before the window, 0.02 s across its start
    (0.01 s inside), 0.04 s in tick 1 and 0.02 s in the traced part."""
    for i in range(5):
        t = T0 + 2.0 * i
        admits = i in (0, 2, 3)
        a1 = t + 0.1 + (0.5 if admits else 0.0)
        if admits:
            if fenced:
                ring.append(("tick.dispatch", i, t + 0.15, t + 0.2,
                             {"program": "jit_prefill"}))
                ring.append(("tick.fence", i, t + 0.2, t + 0.54,
                             {"program": "jit_prefill"}))
            ring.append(("tick.prefill", i, t + 0.15, t + 0.55,
                         {"rid": i, "prompt_len": 5, "bucket": 8}))
        ring.append(("tick.admit", i, t + 0.1, a1,
                     {"admitted": int(admits)}))
        if fenced:
            ring.append(("tick.dispatch", i, a1, a1 + 0.05,
                         {"program": "jit_step"}))
            ring.append(("tick.fence", i, a1 + 0.05, a1 + 0.95,
                         {"program": "jit_step"}))
        ring.append(("tick.decode", i, a1, a1 + 1.0, {"occupancy": 2}))
        ring.append(("tick.accept", i, a1 + 1.0, a1 + 1.2, {}))
        ring.append(("tick", i, t, t + 1.9, {"admitted": int(admits)}))
    for tick, t0, t1 in ((100, T0 - 0.5, T0 - 0.3),
                         (101, T0 - 0.01, T0 + 0.01),
                         (102, T0 + 3.92, T0 + 3.96),
                         (103, T0 + 7.95, T0 + 7.97)):
        ring.append(("host.gc", tick, t0, t1,
                     {"generation": 0, "collected": 3}))
    return {"summary": {"t0": T0, "t_end": T0 + 10.0,
                        "traced": (T0 + 6.0, T0 + 10.0)},
            "trace": {"window_s": 4.0, "devices": 1, "busy_s": 1.9,
                      "by_program": {"jit_step": 1.6, "jit_prefill": 0.3}}}


@pytest.fixture
def ring(monkeypatch):
    """A ring of the test's own, the garbage collector held off so that a
    real collection adds no phase to it."""
    ring = tr.PhaseRing()
    monkeypatch.setattr(tr, "_phase_ring", ring)
    enabled = gc.isenabled()
    gc.disable()
    yield ring
    if enabled:
        gc.enable()


def read(name, run):
    return registry.metric_reader(name)(run)


def test_the_enqueue_is_the_dispatch_time_a_tick(ring):
    run = serve_run(ring)
    # three prefill enqueues and five decode enqueues of 0.05 s, five ticks
    assert read("tick_dispatch_ms_mean.chat", run) == pytest.approx(
        1000.0 * 8 * 0.05 / 5)


def test_the_fence_is_the_fence_time_a_tick(ring):
    run = serve_run(ring)
    # three prefill fences of 0.34 s and five decode fences of 0.9 s
    assert read("tick_fence_ms_mean.sat", run) == pytest.approx(
        1000.0 * (3 * 0.34 + 5 * 0.9) / 5)


def test_the_halves_lie_inside_the_phases_they_split(ring):
    """Enqueue and fence lie inside the decode and admission phases; the
    rest of those phases is host work outside any fenced call."""
    run = serve_run(ring)
    halves = (read("tick_dispatch_ms_mean.granite", run)
              + read("tick_fence_ms_mean.granite", run))
    # admission 0.5 s in three ticks, the decode phase 1.0 s in five
    assert read("tick_admit_ms_mean.blockdiff", run) == pytest.approx(300.0)
    assert halves == pytest.approx(1184.0)
    assert halves <= 300.0 + 1000.0


@pytest.mark.parametrize("name", ["tick_dispatch_ms_mean",
                                  "tick_fence_ms_mean"])
def test_a_ring_without_the_halves_reads_none(name, ring):
    """The parent commit's ring: phases, but no dispatch or fence."""
    assert read(name + ".chat", serve_run(ring, fenced=False)) is None


@pytest.mark.parametrize("name", ["tick_dispatch_ms_mean",
                                  "tick_fence_ms_mean", "gc_pause_ms_per_s"])
def test_the_whole_window_is_read_without_its_traced_part(name, ring):
    """Host time over the whole window: the traced part bounds nothing."""
    run = serve_run(ring)
    traced = read(name + ".blockdiff", run)
    run["summary"]["traced"] = (None, None)
    assert read(name + ".blockdiff", run) == pytest.approx(traced)


def test_gc_pauses_over_the_whole_window(ring):
    run = serve_run(ring)
    # 0.01 s of the pause across t0, 0.04 and 0.02 s, over 10 s; the pause
    # before t0 left out
    assert read("gc_pause_ms_per_s.chat", run) == pytest.approx(
        1000.0 * (0.01 + 0.04 + 0.02) / 10.0)


def test_a_window_with_no_collection_reads_zero(ring):
    """The set-up's collection, before the window, shows the hook was on."""
    run = serve_run(ring)
    run["summary"]["t0"], run["summary"]["t_end"] = T0 + 4.0, T0 + 7.9
    assert read("gc_pause_ms_per_s.sat", run) == 0.0


def test_a_ring_that_holds_no_collection_reads_none(ring):
    """A program without the hook records no collection, in the window or
    before it."""
    run = serve_run(ring)
    kept = [e for e in ring.entries if e[0] != "host.gc"]
    ring.entries.clear()
    ring.entries.extend(kept)
    assert read("gc_pause_ms_per_s.granite", run) is None


@pytest.mark.parametrize("name", ["tick_dispatch_ms_mean",
                                  "tick_fence_ms_mean", "gc_pause_ms_per_s"])
def test_a_ring_wrapped_past_the_start_reads_none(name, monkeypatch):
    small = tr.PhaseRing(maxlen=8)
    monkeypatch.setattr(tr, "_phase_ring", small)
    assert read(name + ".sat", serve_run(small)) is None


CELLS = {"chat": ("serve-chat-steady", "token_gap_mean_ms"),
         "sat": ("serve-longprompt-sat", "out_tokens_per_s"),
         "granite": ("serve-granite-chat-sat", "out_tokens_per_s"),
         "blockdiff": ("serve-blockdiff-sat", "out_tokens_per_s")}


@pytest.mark.parametrize("name", [f"{base}.{suffix}" for base in (
    "tick_dispatch_ms_mean", "tick_fence_ms_mean", "gc_pause_ms_per_s")
    for suffix in CELLS])
def test_each_entry_names_its_one_cell_and_the_metric_it_moves(name):
    entry = next(m for m in registry.load_benchmark()["per_layer"]
                 if m["name"] == name)
    cell, moves = CELLS[name.split(".")[1]]
    assert entry["workloads"] == [cell] and entry["moves"] == moves
    assert entry["layer"] == "serve engine" and entry["better"] == "lower"
    assert entry["source"] == "host_clock"
    assert entry["unit"] == ("ms/s" if name.startswith("gc_") else "ms")
