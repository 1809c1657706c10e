"""The per-layer readers that read what the program names (tick phases from
its ring, ``lm_*`` scopes from its compiled text), each on a ``run`` made by
hand where every answer is known."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import registry  # noqa: E402
from benchmark.trace import named  # noqa: E402
from deeplearning4j_tpu.telemetry import trace as tr  # noqa: E402

T0 = 1000.0  # the window opens here on perf_counter


def serve_run(ring) -> dict:
    """A window of 10 s whose last 4 s are traced (6 to 10). Five ticks of
    2 s: admission takes 0.5 s in the ticks that admit (ticks 0, 2 and 3; a
    prefill of 0.4 s inside) and nothing to speak of in the others, the
    decode phase 1.0 s, acceptance 0.2 s. In the traced part (ticks 3 and
    4) the decode program ran 0.8 s a tick and the prefill program 0.3 s,
    on a window the device's clock measures as 4 s."""
    for i in range(5):
        t = T0 + 2.0 * i
        admits = i in (0, 2, 3)
        a1 = t + 0.1 + (0.5 if admits else 0.0)
        if admits:
            ring.append(("tick.prefill", i, t + 0.15, t + 0.55,
                         {"rid": i, "prompt_len": 5, "bucket": 8}))
        ring.append(("tick.admit", i, t + 0.1, a1,
                     {"admitted": int(admits)}))
        ring.append(("tick.decode", i, a1, a1 + 1.0,
                     {"occupancy": 2, "t_disp": a1 + 0.01}))
        ring.append(("tick.accept", i, a1 + 1.0, a1 + 1.2, {}))
        ring.append(("tick", i, t, t + 1.9, {"admitted": int(admits)}))
    return {"summary": {"t0": T0, "t_end": T0 + 10.0,
                        "traced": (T0 + 6.0, T0 + 10.0)},
            "trace": {"window_s": 4.0, "devices": 1, "busy_s": 1.9,
                      "by_program": {"jit_step": 1.6, "jit_prefill": 0.3}}}


@pytest.fixture
def ring(monkeypatch):
    ring = tr.PhaseRing()
    monkeypatch.setattr(tr, "_phase_ring", ring)
    return ring


def read(name, run):
    return registry.metric_reader(name)(run)


def test_tick_means_over_the_whole_window(ring):
    run = serve_run(ring)
    # three ticks of 0.5 s under tick.admit and two of none, over five
    assert read("tick_admit_ms_mean.chat", run) == pytest.approx(300.0)
    # a tick of 1.9 s minus admit, decode 1.0 and accept 0.2
    assert read("tick_self_ms_mean.sat", run) == pytest.approx(
        1000.0 * (5 * 0.7 - 3 * 0.5) / 5)


def test_idle_by_phase_adds_up_to_the_program_grained_idle_share(ring):
    run = serve_run(ring)
    decode = read("idle_in_decode_phase_pct.chat", run)
    admit = read("idle_in_admit_phase_pct.chat", run)
    outside = read("idle_outside_phases_pct.chat", run)
    # two ticks traced: 2 x 1.0 s under tick.decode against 1.6 s of
    # jit_step, 0.5 s under tick.admit against 0.3 s of jit_prefill
    assert decode == pytest.approx(100.0 * (2.0 - 1.6) / 4.0)
    assert admit == pytest.approx(100.0 * (0.5 - 0.3) / 4.0)
    assert outside == pytest.approx(100.0 * (4.0 - 2.0 - 0.5) / 4.0)
    assert decode + admit + outside == pytest.approx(
        100.0 * (1.0 - (1.6 + 0.3) / 4.0))


def test_a_span_that_does_not_enclose_its_program_reads_negative(ring):
    run = serve_run(ring)
    run["trace"]["by_program"]["jit_step"] = 2.4
    assert read("idle_in_decode_phase_pct.sat", run) < 0


def test_a_phase_cut_by_the_traced_part_counts_its_part_inside(ring):
    run = serve_run(ring)
    # the traced part opens in the middle of tick 3's decode phase
    run["summary"]["traced"] = (T0 + 7.0, T0 + 10.0)
    run["trace"]["window_s"] = 3.0
    assert named.phase_seconds(run, "tick.decode") == pytest.approx(1.6)
    assert named.phase_seconds(run, "tick.admit") == pytest.approx(0.0)


SERVE_READERS = ["tick_admit_ms_mean", "tick_self_ms_mean",
                 "idle_in_decode_phase_pct", "idle_in_admit_phase_pct",
                 "idle_outside_phases_pct"]


@pytest.mark.parametrize("name,maxlen", [
    # of 23 entries the window's first are gone, the traced part's are not
    *((name, 20) for name in SERVE_READERS[:2]),
    # tick 3's prefill, which ended inside the traced part, is gone
    *((name, 8) for name in SERVE_READERS[2:])])
def test_a_ring_wrapped_past_the_start_reads_none(name, maxlen, monkeypatch):
    small = tr.PhaseRing(maxlen=maxlen)
    monkeypatch.setattr(tr, "_phase_ring", small)
    run = serve_run(small)
    assert read(name, run) is None


def test_a_ring_wrapped_before_the_traced_part_still_reads_it(monkeypatch):
    small = tr.PhaseRing(maxlen=20)
    monkeypatch.setattr(tr, "_phase_ring", small)
    run = serve_run(small)
    assert T0 < small.evicted_t1 < T0 + 6.0
    assert read("idle_in_admit_phase_pct.chat", run) == pytest.approx(5.0)


@pytest.mark.parametrize("name", SERVE_READERS[2:])
def test_an_untraced_run_reads_none(name, ring):
    run = serve_run(ring)
    run["summary"]["traced"] = (None, None)
    assert read(name, run) is None


@pytest.mark.parametrize("name,program", [
    ("idle_in_decode_phase_pct", "jit_step"),
    ("idle_in_admit_phase_pct", "jit_prefill")])
def test_a_missing_program_reads_none(name, program, ring):
    run = serve_run(ring)
    del run["trace"]["by_program"][program]
    assert read(name, run) is None
    other = ({"idle_in_decode_phase_pct", "idle_in_admit_phase_pct"}
             - {name}).pop()
    assert read(other, run) is not None


@pytest.mark.parametrize("name", SERVE_READERS)
def test_a_program_without_the_ring_reads_none(name, ring, monkeypatch):
    """The new readers are laid over the parent's checkout too, whose
    ``telemetry.trace`` has no ring: nothing to read, and no exception."""
    run = serve_run(ring)
    monkeypatch.delattr(tr, "phases_between")
    assert read(name, run) is None


def train_run() -> dict:
    """One device, 10 s busy: 6 s under lm_moe (1 s of it the exchange), 1
    s lm_attn (0.5 the core), 1.5 s lm_loss forward and backward, 0.5 s
    lm_update, 0.2 s lm_embed, and 0.8 s under no lm_* scope."""
    spans = [("jit(step)/while/body/lm_moe/dot_general", 0.0, 5.0),
             ("jit(step)/while/body/lm_moe/moe_all2all_dispatch/all_to_all",
              5.0, 6.0),
             ("jit(step)/while/body/lm_attn/dot_general", 6.0, 6.5),
             ("jit(step)/while/body/lm_attn/blockwise_q_block_0/exp",
              6.5, 7.0),
             ("jit(step)/lm_loss/log_softmax", 7.0, 8.0),
             ("jit(step)/transpose(jvp())/lm_loss/dot_general", 8.0, 8.5),
             ("jit(step)/lm_update/sub", 8.5, 9.0),
             ("jit(step)/lm_embed/gather", 9.0, 9.2),
             ("jit(step)/while/body/copy", 9.2, 10.0)]
    scoped = [(scope, "jit_step", int(a * 1e9), int(b * 1e9), 0)
              for scope, a, b in spans]
    return {"summary": {"scopes": {"fusion.1": spans[0][0]}},
            "model": registry.load_part("models", "flagship"),
            "trace": {"devices": 1, "busy_s": 10.0, "window_s": 10.0,
                      "scoped": scoped}}


def test_lm_shares_of_busy_time():
    run = train_run()
    shares = {name: read(name + "_share_of_busy_pct", run)
              for name in ("moe", "attn", "loss", "update", "unscoped")}
    assert shares == pytest.approx({"moe": 60.0, "attn": 10.0, "loss": 15.0,
                                    "update": 5.0, "unscoped": 8.0})
    # lm_embed is the part left out
    assert sum(shares.values()) == pytest.approx(100.0 - 2.0)


def test_a_stale_cache_reads_all_unscoped_and_not_none():
    """An executable cached before the scopes existed comes back without
    them (metadata is not in the cache key): numbers, and the tell."""
    run = train_run()
    run["trace"]["scoped"] = [("jit(step)/while/body/dot_general", *rest)
                              for _, *rest in run["trace"]["scoped"]]
    assert read("moe_share_of_busy_pct", run) == 0.0
    assert read("unscoped_share_of_busy_pct", run) == 100.0


@pytest.mark.parametrize("name", ["moe", "attn", "loss", "update",
                                  "unscoped"])
def test_lm_shares_read_none_without_scopes(name, monkeypatch):
    run = train_run()
    run["summary"]["scopes"] = None  # an untraced run keeps none
    assert read(name + "_share_of_busy_pct", run) is None
    # a program that names no lm_* scope (the parent commit)
    from deeplearning4j_tpu.models import transformer_lm

    run = train_run()
    monkeypatch.delattr(transformer_lm, "LM_SCOPES")
    assert read(name + "_share_of_busy_pct", run) is None


def test_every_new_entry_names_its_cells_and_the_metric_it_moves():
    bench = registry.load_benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    for base in SERVE_READERS:
        chat, sat = entries[base + ".chat"], entries[base + ".sat"]
        assert chat["workloads"] == ["serve-chat-steady"]
        assert chat["moves"] == "token_gap_mean_ms"
        assert sat["workloads"] == ["serve-longprompt-sat"]
        assert sat["moves"] == "out_tokens_per_s"
        assert chat["layer"] == sat["layer"] == "serve engine"
    for name in ("moe", "attn", "loss", "update", "unscoped"):
        m = entries[name + "_share_of_busy_pct"]
        assert m["workloads"] == ["train-1chip-seq4k", "train-4chip-dp2ep2"]
        assert m["moves"] == "train_tokens_per_s"
        assert m["layer"] == "model step" and m["unit"] == "%"
