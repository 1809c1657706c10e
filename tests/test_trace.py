"""ISSUE 7: span-based distributed tracing + crash flight recorder.

Unit-level pins for telemetry/trace.py (span model, JSONL begin/end
records, context propagation, flight-recorder dumps and their rate
limit), the tracker-frame propagation in remote_tracker.py, and the
tools/trace_report.py reconstruction — including the partial-round case
a kill -9 leaves behind (begin records with no end).
"""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from deeplearning4j_tpu.telemetry import trace as tr
from deeplearning4j_tpu.telemetry.registry import MetricsRegistry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools.trace_report import (  # noqa: E402
    build_timeline,
    chrome_trace,
    load_trace_dir,
)


@pytest.fixture
def no_global_tracer():
    """Isolate the process-global tracer; restore whatever was there."""
    prev = tr.set_tracer(None)
    yield
    tr.set_tracer(prev)


def _read_records(path):
    with open(path) as fh:
        return [json.loads(line) for line in fh if line.strip()]


class TestSpans:
    def test_nesting_parents_and_jsonl_records(self, tmp_path,
                                               no_global_tracer):
        t = tr.Tracer("p0", trace_dir=str(tmp_path),
                      registry=MetricsRegistry())
        with t.span("outer", attrs={"k": 1}) as outer:
            assert t.current_span() is outer
            with t.span("inner") as inner:
                assert inner.parent_id == outer.span_id
                assert inner.trace_id == outer.trace_id
            assert t.current_span() is outer
        assert t.current_span() is None
        recs = _read_records(tmp_path / "spans_p0.jsonl")
        # begin records written eagerly (crash durability), ends after
        assert [r["ev"] for r in recs] == ["B", "B", "E", "E"]
        assert recs[0]["name"] == "outer" and recs[1]["name"] == "inner"
        assert recs[2]["name"] == "inner" and recs[2]["status"] == "ok"
        assert recs[2]["dur_ms"] >= 0

    def test_error_status_and_events(self, tmp_path, no_global_tracer):
        t = tr.Tracer("p0", trace_dir=str(tmp_path),
                      registry=MetricsRegistry())
        with pytest.raises(ValueError):
            with t.span("boom") as sp:
                sp.add_event("about_to_fail", detail="x")
                raise ValueError("synthetic")
        end = [r for r in _read_records(tmp_path / "spans_p0.jsonl")
               if r["ev"] == "E"][0]
        assert end["status"] == "error"
        assert "synthetic" in end["error"]
        assert end["events"][0]["name"] == "about_to_fail"
        assert t.registry.counter("trace_spans_error_total").value == 1

    def test_wire_context_parents_across_tracers(self, tmp_path,
                                                 no_global_tracer):
        """Two tracers = two processes: a context dict shipped over any
        transport parents the remote span under the local one."""
        master = tr.Tracer("master", trace_dir=str(tmp_path),
                           registry=MetricsRegistry())
        worker = tr.Tracer("worker", trace_dir=str(tmp_path),
                           registry=MetricsRegistry())
        root = master.start_span("round", attrs={"round": 0})
        ctx = root.context()  # JSON-safe wire dict
        ctx = json.loads(json.dumps(ctx))
        child = worker.start_span("work", parent=ctx)
        child.end()
        root.end()
        assert child.trace_id == root.trace_id
        assert child.parent_id == root.span_id

    def test_thread_local_current_span(self, tmp_path, no_global_tracer):
        t = tr.Tracer("p0", trace_dir=str(tmp_path),
                      registry=MetricsRegistry())
        seen = {}

        def other_thread():
            seen["current"] = t.current_span()

        with t.span("main-thread"):
            th = threading.Thread(target=other_thread)
            th.start()
            th.join()
        # another thread never silently parents under this thread's span
        assert seen["current"] is None

    def test_maybe_span_is_noop_without_tracer(self, no_global_tracer):
        assert tr.get_tracer() is None
        with tr.maybe_span("anything", attrs={"x": 1}) as sp:
            assert sp is None
        assert tr.current_trace_context() is None


class TestFlightRecorder:
    def test_dump_contents(self, tmp_path, no_global_tracer):
        reg = MetricsRegistry()
        reg.counter("workers_failed").inc(2)
        t = tr.Tracer("w0", trace_dir=str(tmp_path), registry=reg)
        with t.span("done-span"):
            pass
        open_span = t.start_span("stuck-span", attrs={"round": 3})
        path = t.dump("SIGTERM", error=RuntimeError("killed"),
                      extra={"note": "test"})
        assert path == str(tmp_path / "flightrec_w0.json")
        dump = json.load(open(path))
        assert dump["reason"] == "SIGTERM"
        assert "killed" in dump["error"]
        assert dump["extra"]["note"] == "test"
        assert [s["name"] for s in dump["open"]] == ["stuck-span"]
        assert dump["open"][0]["open"] is True
        assert dump["open"][0]["dur_ms"] >= 0
        assert any(r["name"] == "done-span" for r in dump["recent"])
        counters = {c["name"]: c["value"]
                    for c in dump["counters"]["counters"]}
        assert counters["workers_failed"] == 2
        assert "device_memory" in dump
        open_span.end()

    def test_checkpoint_rate_limit(self, tmp_path, no_global_tracer):
        t = tr.Tracer("w0", trace_dir=str(tmp_path),
                      registry=MetricsRegistry(),
                      min_checkpoint_interval_s=60.0)
        assert t.flight_checkpoint() is not None  # first always lands
        assert t.flight_checkpoint() is None      # inside the interval
        assert t.dump("crash") is not None        # explicit never limited

    def test_dump_never_raises(self, tmp_path, no_global_tracer):
        t = tr.Tracer("w0", trace_dir=str(tmp_path),
                      flight_path="/nonexistent-dir/cannot/write.json",
                      registry=MetricsRegistry())
        assert t.dump("crash") is None  # swallowed, not raised


class TestTrackerPropagation:
    def test_rpc_span_links_client_and_server(self, tmp_path,
                                              no_global_tracer, lockwatch):
        # armed lockwatch (ISSUE 11): tracer ring lock + tracker client
        # request lock + server state lock are all watched across the RPC
        from deeplearning4j_tpu.scaleout.remote_tracker import (
            StateTrackerClient,
            StateTrackerServer,
        )

        tracer = tr.Tracer("node", trace_dir=str(tmp_path),
                           registry=MetricsRegistry())
        tr.set_tracer(tracer)
        with StateTrackerServer() as server:
            client = StateTrackerClient(server.address,
                                        registry=MetricsRegistry())
            # outside any span: the 3-tuple untraced wire path
            client.add_worker("w-untraced")
            with tracer.span("op") as op:
                client.add_worker("w-traced")
                client.count("poll.key")  # poll method: never spanned
            client.close()
            time.sleep(0.1)  # server handler writes its span async
        spans = load_trace_dir(str(tmp_path))
        by_name = {}
        for sp in spans.values():
            by_name.setdefault(sp["name"], []).append(sp)
        assert len(by_name["tracker.rpc"]) == 1  # only the traced call
        rpc = by_name["tracker.rpc"][0]
        assert rpc["attrs"]["method"] == "add_worker"
        assert rpc["parent_id"] == op.span_id
        serve = by_name["tracker.serve"][0]
        assert serve["parent_id"] == rpc["span_id"]
        assert serve["trace_id"] == rpc["trace_id"] == op.trace_id
        watch = lockwatch.summary()
        assert watch["cycles"] == 0
        for name in ("telemetry.trace", "tracker.client", "tracker.state"):
            assert watch["locks"].get(name, {}).get("acquires", 0) > 0, \
                f"{name} lock was not watched across the RPC"

    def test_retry_recorded_as_event(self, tmp_path, no_global_tracer):
        import _dist_helpers
        from deeplearning4j_tpu.scaleout.remote_tracker import (
            StateTrackerClient,
            StateTrackerServer,
        )

        tracer = tr.Tracer("node", trace_dir=str(tmp_path),
                           registry=MetricsRegistry())
        tr.set_tracer(tracer)
        with StateTrackerServer() as server:
            with _dist_helpers.FaultyTrackerProxy(
                    server.address, cut_response_after=0) as proxy:
                client = StateTrackerClient(proxy.address,
                                            request_timeout_s=5, retries=3,
                                            backoff_s=0.01,
                                            registry=MetricsRegistry())
                with tracer.span("op"):
                    assert client.workers() == []  # cut → reconnect+retry
                client.close()
        spans = load_trace_dir(str(tmp_path))
        rpc = [s for s in spans.values() if s["name"] == "tracker.rpc"][0]
        names = [e["name"] for e in rpc["events"]]
        assert "retry" in names and "reconnect" in names


class TestTraceReport:
    def _fake_elastic_trace(self, d, kill_worker_mid_round=None):
        """Synthesize a master + two-worker trace the way elastic.py
        writes it; optionally leave w1's round-N spans unclosed (the
        kill -9 shape)."""
        reg = MetricsRegistry()
        master = tr.Tracer("master", trace_dir=str(d), registry=reg)
        workers = {w: tr.Tracer(w, trace_dir=str(d),
                                registry=MetricsRegistry())
                   for w in ("w0", "w1")}
        run = master.start_span("elastic.train", parent=False)
        for rnd in range(3):
            round_sp = master.start_span("elastic.round", parent=run,
                                         attrs={"round": rnd})
            barrier = master.start_span("elastic.barrier", parent=round_sp,
                                        attrs={"round": rnd})
            for i, (w, wt) in enumerate(sorted(workers.items())):
                killed = (kill_worker_mid_round is not None
                          and w == "w1" and rnd == kill_worker_mid_round)
                wr = wt.start_span("worker.round",
                                   parent=round_sp.context(),
                                   attrs={"round": rnd, "worker": w})
                steps = wt.start_span("worker.steps", parent=wr,
                                      attrs={"round": rnd})
                steps.end()
                if killed:
                    continue  # kill -9: round/publish spans never close
                pub = wt.start_span("worker.publish", parent=wr,
                                    attrs={"round": rnd, "worker": w})
                time.sleep(0.002 * (i + 1))  # staggered arrivals
                pub.end()
                barrier.add_event("contribution", worker=w)
                wr.end()
            if kill_worker_mid_round is not None \
                    and rnd >= kill_worker_mid_round:
                barrier.add_event("buried", worker="w1")
            barrier.end()
            if kill_worker_mid_round is not None \
                    and rnd == kill_worker_mid_round:
                # master still commits on the survivor set
                pass
            round_sp.end()
        run.end()
        return d

    def test_merged_timeline_and_attribution(self, tmp_path,
                                             no_global_tracer):
        self._fake_elastic_trace(tmp_path)
        spans = load_trace_dir(str(tmp_path))
        timeline = build_timeline(spans)
        assert timeline["processes"] == ["master", "w0", "w1"]
        rounds = timeline["rounds"]
        assert [r["round"] for r in rounds] == [0, 1, 2]
        for r in rounds:
            assert r["status"] == "committed"
            # w1's publish is staggered later → it is the straggler
            assert r["straggler"] == "w1"
            assert r["straggler_wait_ms"] > 0
            waited = {a["worker"]: a["waited_ms"] for a in r["contributors"]}
            assert waited["w1"] == 0.0 and waited["w0"] > 0

    def test_partial_round_from_kill(self, tmp_path, no_global_tracer):
        self._fake_elastic_trace(tmp_path, kill_worker_mid_round=1)
        spans = load_trace_dir(str(tmp_path))
        timeline = build_timeline(spans)
        r1 = [r for r in timeline["rounds"] if r["round"] == 1][0]
        # the survivor's contribution still committed the round, but the
        # victim's unclosed spans are visible on it
        assert "w1:worker.round" in r1["open_spans"]
        assert [a["worker"] for a in r1["contributors"]] == ["w0"]
        assert timeline["n_open"] >= 1

    def test_chrome_export_schema(self, tmp_path, no_global_tracer):
        self._fake_elastic_trace(tmp_path, kill_worker_mid_round=2)
        spans = load_trace_dir(str(tmp_path))
        out = chrome_trace(spans)
        events = out["traceEvents"]
        meta = [e for e in events if e["ph"] == "M"]
        assert {m["args"]["name"] for m in meta} == {"master", "w0", "w1"}
        xs = [e for e in events if e["ph"] == "X"]
        assert len(xs) == len(spans)
        for e in xs:
            assert e["dur"] >= 0 and e["ts"] > 0
            assert isinstance(e["pid"], int)
        # the victim's unclosed span is flagged open in its args
        assert any(e["args"].get("open") for e in xs)
        json.dumps(out)  # valid JSON end to end

    def test_cli(self, tmp_path, no_global_tracer):
        self._fake_elastic_trace(tmp_path)
        chrome_path = str(tmp_path / "chrome.json")
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "trace_report.py"),
             str(tmp_path), "--chrome", chrome_path],
            capture_output=True, text=True, timeout=60, cwd=REPO)
        assert out.returncode == 0, out.stderr
        assert "committed" in out.stdout
        assert "waited on" in out.stdout
        assert os.path.exists(chrome_path)
        out2 = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "trace_report.py"),
             str(tmp_path), "--json"],
            capture_output=True, text=True, timeout=60, cwd=REPO)
        tl = json.loads(out2.stdout)
        assert len(tl["rounds"]) == 3

    def test_cli_missing_dir_exits_2(self, tmp_path):
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "trace_report.py"),
             str(tmp_path / "nope")],
            capture_output=True, text=True, timeout=60, cwd=REPO)
        assert out.returncode == 2
        assert "no such trace dir" in out.stderr

    def test_torn_tail_line_tolerated(self, tmp_path, no_global_tracer):
        t = tr.Tracer("p0", trace_dir=str(tmp_path),
                      registry=MetricsRegistry())
        with t.span("complete"):
            pass
        with open(tmp_path / "spans_p0.jsonl", "a") as fh:
            fh.write('{"ev": "B", "span_id": "torn')  # killed mid-write
        spans = load_trace_dir(str(tmp_path))
        assert len(spans) == 1  # the complete span survives, tail skipped


class TestBenchReport:
    def _write_round(self, d, n, value, detail=None, parsed=True, tail=""):
        rec = {"n": n, "cmd": "bench", "rc": 0, "tail": tail,
               "parsed": ({"metric": "m", "value": value, "unit": "x",
                           "detail": detail or {}} if parsed else None)}
        (d / f"BENCH_r{n:02d}.json").write_text(json.dumps(rec))

    def test_trajectory_and_regression_flag(self, tmp_path):
        self._write_round(tmp_path, 1, 100.0,
                          {"mlp_bf16_samples_per_sec": 1000.0})
        self._write_round(tmp_path, 2, 110.0,
                          {"mlp_bf16_samples_per_sec": 800.0,
                           "moe_tokens_per_sec": 50.0})
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "bench_report.py"),
             "--dir", str(tmp_path), "--json"],
            capture_output=True, text=True, timeout=60, cwd=REPO)
        assert out.returncode == 0, out.stderr
        traj = json.loads(out.stdout)
        regs = {r["metric"] for r in traj["regressions"]}
        assert regs == {"mlp_bf16_samples_per_sec"}  # -20% flagged
        row = [r for r in traj["table"]
               if r["metric"] == "mlp_bf16_samples_per_sec"][0]
        assert row["delta_pct"] == -20.0 and row["regression"]
        # fail-on-regression turns the flag into exit 1
        out2 = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "bench_report.py"),
             "--dir", str(tmp_path), "--fail-on-regression"],
            capture_output=True, text=True, timeout=60, cwd=REPO)
        assert out2.returncode == 1
        assert "REGRESSION" in out2.stdout

    def test_unparsed_round_recovered_from_tail(self, tmp_path):
        self._write_round(tmp_path, 1, 100.0,
                          {"word2vec_words_per_sec": 500.0})
        self._write_round(
            tmp_path, 2, None, parsed=False,
            tail='...clipped... "word2vec_words_per_sec": 600.0, '
                 '"word2vec_host_device_split": {"host_pairgen_s": 0.0}}')
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "bench_report.py"),
             "--dir", str(tmp_path), "--json"],
            capture_output=True, text=True, timeout=60, cwd=REPO)
        traj = json.loads(out.stdout)
        assert traj["rounds"][1]["source"] == "partial"
        row = [r for r in traj["table"]
               if r["metric"] == "word2vec_words_per_sec"][0]
        assert dict((n, v) for n, v in row["series"])[2] == 600.0
        assert row["delta_pct"] == 20.0

    def test_runs_on_real_repo_artifacts(self):
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "bench_report.py")],
            capture_output=True, text=True, timeout=60, cwd=REPO)
        assert out.returncode == 0, out.stderr
        assert "bench trajectory" in out.stdout

    def test_empty_dir_exits_2(self, tmp_path):
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "bench_report.py"),
             "--dir", str(tmp_path)],
            capture_output=True, text=True, timeout=60, cwd=REPO)
        assert out.returncode == 2


# ---------------------------------------- W3C traceparent (ISSUE 12) ----

class TestTraceparent:
    """The HTTP-propagation half of the serve tracing: header format,
    parse tolerance (a malformed header is IGNORED per the W3C spec —
    the request must proceed as a fresh root), and that a caller-minted
    32-hex trace id flows through the span model unchanged."""

    def test_format_pads_internal_ids_to_w3c_width(self):
        hdr = tr.format_traceparent({"trace_id": "ab" * 8,
                                     "span_id": "cd" * 4})
        version, trace_id, span_id, flags = hdr.split("-")
        assert version == "00" and flags == "01"
        assert len(trace_id) == 32 and trace_id.endswith("ab" * 8)
        assert len(span_id) == 16 and span_id.endswith("cd" * 4)

    def test_parse_format_round_trip(self):
        ctx = {"trace_id": "a" * 32, "span_id": "b" * 16}
        assert tr.parse_traceparent(tr.format_traceparent(ctx)) == ctx

    @pytest.mark.parametrize("bad", [
        None, "", "garbage", "00-short-ffffffffffffffff-01",
        "00-" + "g" * 32 + "-" + "f" * 16 + "-01",   # non-hex
        "00-" + "0" * 32 + "-" + "f" * 16 + "-01",   # all-zero trace id
        "00-" + "f" * 32 + "-" + "0" * 16 + "-01",   # all-zero span id
        "ff-" + "f" * 32 + "-" + "f" * 16 + "-01",   # forbidden version
        "0-" + "f" * 32 + "-" + "f" * 16 + "-01",    # short version
        "00-" + "f" * 32 + "-" + "f" * 16,           # missing flags
    ])
    def test_malformed_headers_parse_to_none(self, bad):
        assert tr.parse_traceparent(bad) is None

    def test_future_version_with_extra_fields_accepted(self):
        # the spec: parse version 01+ headers by the 00 rules, ignoring
        # trailing fields
        hdr = "01-" + "a" * 32 + "-" + "b" * 16 + "-01-extra"
        assert tr.parse_traceparent(hdr) == {"trace_id": "a" * 32,
                                             "span_id": "b" * 16}

    def test_remote_trace_id_flows_through_spans(self, tmp_path,
                                                 no_global_tracer):
        tracer = tr.Tracer("srv", trace_dir=str(tmp_path))
        ctx = tr.parse_traceparent("00-" + "a" * 32 + "-" + "b" * 16 + "-01")
        with tracer.span("http.request", parent=ctx) as sp:
            assert sp.trace_id == "a" * 32
            assert sp.parent_id == "b" * 16
            # the response header regenerates losslessly at full width
            assert tr.format_traceparent(sp.context()) == \
                f"00-{'a' * 32}-{sp.span_id}-01"
        tracer.close()
        recs = _read_records(str(tmp_path / "spans_srv.jsonl"))
        assert recs[0]["trace_id"] == "a" * 32


# ------------------------------------------------- tick phases (ISSUE 25) ----

@pytest.fixture
def phase_ring(monkeypatch):
    """A ring of this test's own in place of the process's."""
    ring = tr.PhaseRing()
    monkeypatch.setattr(tr, "_phase_ring", ring)
    return ring


def _tiny_engine(**kw):
    import jax

    from deeplearning4j_tpu.models.transformer_lm import init_lm_params
    from deeplearning4j_tpu.serve import DecodeEngine

    params = init_lm_params(jax.random.PRNGKey(0), 31, 8, 2, 2, 16,
                            n_layers=1)
    kw.setdefault("registry", MetricsRegistry())
    return DecodeEngine(params, 2, n_slots=2, max_len=16, serve_dtype=None,
                        tuned=False, **kw)


class TestPhases:
    def test_nesting_records_the_parent_tick_and_attrs(self, phase_ring):
        with tr.phase("tick") as tick:
            with tr.phase("tick.admit", tick.tick) as admit:
                with tr.phase("tick.prefill", tick.tick, rid=7, bucket=8):
                    pass
                admit.attrs["admitted"] = 1  # filled in before the end
            with tr.phase("tick.decode", tick.tick, occupancy=2):
                pass
        with tr.phase("tick") as second:
            pass
        assert second.tick != tick.tick  # a tick with no parent draws an id
        entries, wrapped = tr.phases_between(0.0, float("inf"))
        assert not wrapped
        # in the order they ended: children before their parent
        assert [e[0] for e in entries] == [
            "tick.prefill", "tick.admit", "tick.decode", "tick", "tick"]
        assert {e[1] for e in entries[:4]} == {tick.tick}
        by_name = {e[0]: e for e in entries[:4]}
        assert by_name["tick.prefill"][4] == {"rid": 7, "bucket": 8}
        assert by_name["tick.admit"][4] == {"admitted": 1}
        assert by_name["tick.decode"][4] == {"occupancy": 2}
        # each child lies inside its parent, on perf_counter
        _, _, t0, t1, _ = by_name["tick"]
        for name in ("tick.admit", "tick.decode"):
            assert t0 <= by_name[name][2] <= by_name[name][3] <= t1
        assert by_name["tick.admit"][2] <= by_name["tick.prefill"][2]
        assert by_name["tick.prefill"][3] <= by_name["tick.admit"][3]
        assert tick.ms == pytest.approx((t1 - t0) * 1000.0)

    def test_self_time_is_the_span_minus_its_children(self):
        entries = [("tick.prefill", 1, 1.0, 4.0, {}),
                   ("tick.admit", 1, 0.5, 4.5, {}),
                   ("tick.decode", 1, 5.0, 8.0, {}),
                   ("tick.accept", 1, 8.0, 8.5, {}),
                   ("tick", 1, 0.0, 10.0, {}),
                   # another tick's spans cover nothing of this one
                   ("tick.decode", 2, 0.0, 10.0, {}),
                   ("tick", 2, 0.0, 11.0, {})]
        selfs = tr.phase_self_seconds(entries)
        assert selfs == pytest.approx([3.0, 1.0, 3.0, 0.5,
                                       10.0 - 4.0 - 3.0 - 0.5, 10.0, 1.0])

    def test_phases_between_clips_and_reports_a_wrapped_ring(
            self, monkeypatch):
        ring = tr.PhaseRing(maxlen=3)
        monkeypatch.setattr(tr, "_phase_ring", ring)
        for i in range(3):
            ring.append(("tick", i, float(i), i + 1.0, {}))
        entries, wrapped = tr.phases_between(0.5, 2.25)
        assert not wrapped
        assert [(e[2], e[3]) for e in entries] == [(0.5, 1.0), (1.0, 2.0),
                                                   (2.0, 2.25)]
        assert tr.phases_between(1.0, 2.0)[0] == [("tick", 1, 1.0, 2.0, {})]
        ring.append(("tick", 3, 3.0, 4.0, {}))  # drops the span that ended at 1
        assert tr.phases_between(0.5, 4.0)[1], "a span after 0.5 is gone"
        entries, wrapped = tr.phases_between(1.0, 4.0)
        assert not wrapped and len(entries) == 3

    def test_phase_annotates_through_utils_profiling(self, phase_ring,
                                                     monkeypatch):
        """``utils.profiling.annotate`` is the one place a TraceAnnotation
        is made: ``phase`` opens its span through it, with the tick id as
        the event's argument, and no other module of the package makes
        one."""
        seen = []

        class Spy:
            def __init__(self, name, **kw):
                seen.append((name, kw))

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return None

        monkeypatch.setattr(tr, "annotate", Spy)
        with tr.phase("tick") as tick:
            with tr.phase("tick.decode", tick.tick, occupancy=1):
                pass
        assert seen == [("tick", {"tick": tick.tick}),
                        ("tick.decode", {"tick": tick.tick})]
        package = os.path.join(REPO, "deeplearning4j_tpu")
        makers = []
        for root, _, files in os.walk(package):
            for name in files:
                if name.endswith(".py"):
                    path = os.path.join(root, name)
                    with open(path) as fh:
                        if "TraceAnnotation(" in fh.read():
                            makers.append(os.path.relpath(path, package))
        assert makers == [os.path.join("utils", "profiling.py")]

    def test_engine_leaves_one_tick_tree_a_step(self, phase_ring):
        eng = _tiny_engine()
        reqs = [eng.submit([1, 2, 3], max_new_tokens=3),
                eng.submit([4, 5, 6, 7, 8], max_new_tokens=2),
                eng.submit([9, 10], max_new_tokens=2)]  # waits for a slot
        steps = 0
        while eng.has_work():
            eng.step()
            steps += 1
        entries, wrapped = tr.phases_between(0.0, float("inf"))
        assert not wrapped
        ticks = [e for e in entries if e[0] == "tick"]
        assert len(ticks) == steps
        for _, tick, t0, t1, attrs in ticks:
            kids = [e for e in entries if e[1] == tick and e[0] != "tick"]
            names = [e[0] for e in kids]
            assert names.count("tick.admit") == 1
            assert names.count("tick.decode") == 1
            assert names.count("tick.accept") == 1
            assert all(t0 <= e[2] and e[3] <= t1 for e in kids)
            admit = next(e for e in kids if e[0] == "tick.admit")
            assert admit[4]["admitted"] == attrs["admitted"] \
                == admit[4]["prefill_dispatches"] \
                == names.count("tick.prefill")
            decode = next(e for e in kids if e[0] == "tick.decode")
            assert decode[4]["occupancy"] == attrs["occupancy"]
            assert decode[2] <= decode[4]["t_disp"] <= decode[3]
        assert sum(t[4]["admitted"] for t in ticks) == 3
        assert sum(t[4]["retired"] for t in ticks) == 3
        prefills = [e for e in entries if e[0] == "tick.prefill"]
        assert sorted(e[4]["rid"] for e in prefills) == sorted(
            r.rid for r in reqs)
        for e in prefills:
            req = next(r for r in reqs if r.rid == e[4]["rid"])
            assert e[4]["prompt_len"] == len(req.prompt)
            assert e[4]["bucket"] == eng.bucket_for(len(req.prompt))
            # the prefill's fence is the request's first token
            assert req.t_admit <= e[2] and e[3] == req.t_first

    def test_one_record_feeds_histogram_span_and_runprof(
            self, phase_ring, tmp_path, no_global_tracer):
        from deeplearning4j_tpu.telemetry.runprof import RunProfiler

        reg = MetricsRegistry()
        prof = RunProfiler(registry=reg, update_every=1)
        tracer = tr.Tracer("serve", trace_dir=str(tmp_path), registry=reg)
        tr.set_tracer(tracer)
        try:
            eng = _tiny_engine(registry=reg, runprof=prof)
            eng.generate([1, 2, 3], max_new_tokens=4)
        finally:
            tr.set_tracer(None)
            tracer.close()
        entries, _ = tr.phases_between(0.0, float("inf"))
        ticks = {e[1]: e for e in entries if e[0] == "tick"}
        decodes = [e for e in entries if e[0] == "tick.decode"]
        ms = [(e[3] - e[2]) * 1000.0 for e in decodes]
        assert reg.histogram("serve_decode_step_ms").count == len(ms)
        assert reg.histogram("serve_decode_step_ms").sum == pytest.approx(
            sum(ms))
        timings = prof.timings("serve_decode")
        assert [t.wall_ms for t in timings] == pytest.approx(ms)
        for t, e in zip(timings, decodes):
            tick = ticks[e[1]]
            assert t.dispatch_ms == pytest.approx(
                (e[4]["t_disp"] - e[2]) * 1000.0)
            assert t.dispatch_ms + t.device_ms == pytest.approx(t.wall_ms)
            assert t.host_ms == pytest.approx(
                (tick[3] - tick[2]) * 1000.0 - t.wall_ms)
        steps = [r for r in _read_records(tmp_path / "spans_serve.jsonl")
                 if r["ev"] == "E" and r["name"] == "engine.step"]
        assert [s["attrs"]["decode_ms"] for s in steps] == [
            round(x, 3) for x in ms]
        assert [s["attrs"]["occupancy"] for s in steps] == [
            e[4]["occupancy"] for e in decodes]
