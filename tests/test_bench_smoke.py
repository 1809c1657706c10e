"""CI gate that `python bench.py` completes within its stage budgets on the
CPU backend and always lands a parseable summary line — so a driver timeout
like round 2's rc=124 can never recur silently (VERDICT r02 next-steps #1/#10).
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_completes_on_cpu():
    env = dict(os.environ)
    # BENCH_FORCE_CPU makes every stage child flip jax.config to CPU
    # (JAX_PLATFORMS=cpu in the child's environment would do the same)
    env["BENCH_FORCE_CPU"] = "1"
    env["BENCH_FAST"] = "1"
    env["BENCH_BUDGET_SEC"] = "240"
    # scope to the stages the assertions below actually read (summary
    # metric, CPU baseline, MFU keys) — the full sweep is `python bench.py`
    # on the chip; per-stage plumbing for the newer stages is guarded by
    # test_bench_lm_composed_stage_on_cpu and the skip test keeps every
    # stage's budget discipline honest
    env["BENCH_ONLY"] = ("cpu_mlp_fp32,mlp_bf16,mlp_bf16_nofused,"
                         "mlp_fp32,lenet_bf16")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=300, cwd=REPO, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    line = out.stdout.strip().splitlines()[-1]
    rec = json.loads(line)
    assert rec["metric"] == "mnist_mlp_train_samples_per_sec_per_chip"
    assert rec["value"] and rec["value"] > 0
    det = rec["detail"]
    # CPU baseline ran first and loudly: either a number or an explicit
    # failed status — never a silent 0.0.
    assert det.get("cpu_mlp_fp32_samples_per_sec") or \
        "failed" in str(det.get("cpu_mlp_fp32_status", ""))
    # MFU recorded for every completed TPU-model stage
    for stage in ("mlp_bf16", "mlp_fp32", "lenet_bf16", "lenet_fp32"):
        if det.get(f"{stage}_samples_per_sec"):
            assert f"{stage}_mfu" in det
    # the partial file was flushed incrementally
    assert os.path.exists(os.path.join(REPO, "bench_partial.json"))


def test_bench_lm_composed_stage_on_cpu():
    """The composed-flagship LM stage (round 6) runs END TO END on the CPU
    backend at tiny shapes: rate key present, forced-dense A/B twin key
    present, forced-CPU baseline key present, A/B ratio computed, and the
    env-seam core choice recorded in the stage detail — so tier-1 guards
    the stage plumbing without a chip."""
    env = dict(os.environ)
    env["BENCH_FORCE_CPU"] = "1"
    env["BENCH_FAST"] = "1"
    env["BENCH_BUDGET_SEC"] = "420"
    env["BENCH_ONLY"] = "cpu_lm_composed,lm_composed,lm_composed_densecore"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=480, cwd=REPO, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    det = json.loads(out.stdout.strip().splitlines()[-1])["detail"]
    assert det.get("lm_composed_samples_per_sec"), det.get(
        "lm_composed_status")
    assert "lm_composed_densecore_samples_per_sec" in det
    assert "cpu_lm_composed_samples_per_sec" in det
    assert det.get("lm_composed_mfu") is not None
    if det.get("lm_composed_densecore_samples_per_sec"):
        assert "lm_composed_vs_densecore" in det
    stage_detail = det.get("lm_composed_detail", {})
    assert stage_detail.get("attn_impl") == "blockwise"
    assert stage_detail.get("tokens_per_sec", 0) > 0
    dense_detail = det.get("lm_composed_densecore_detail", {})
    assert dense_detail.get("attn_impl") == "dense"
    # telemetry block (ISSUE 2): the stage A/Bs the metrics-threaded step,
    # runs a logged window through the JSONL pipeline, and must stay under
    # the 5% overhead budget at the default fetch interval
    telemetry = stage_detail.get("telemetry", {})
    assert telemetry, "lm_composed detail lost its telemetry block"
    assert telemetry["steps_logged"] > 0
    summary = telemetry["step_log_summary"]
    assert "loss" in summary and "grad_norm" in summary
    assert summary["tokens_per_sec_mean"] > 0
    assert len(summary["router_load_mean"]) >= 2
    assert telemetry["overhead_pct"] < 5.0, telemetry
    # profile blob (ISSUE 9): every lm_composed round embeds the compiled
    # step's StepProfile + attribution so profile_report/bench_report can
    # diff footprint across rounds
    blob = stage_detail.get("profile", {})
    assert blob, "lm_composed detail lost its profile blob"
    assert blob["flops"] > 0 and blob["label"] == "lm_composed"
    assert blob["donated_args"] >= 1  # the bench step donates params
    assert "xla_vs_analytic_flops" in blob
    att = stage_detail.get("profile_attribution", {})
    assert att.get("bound") in ("compute", "memory", "comm")


def test_bench_ckpt_stage_on_cpu():
    """The sharded-checkpoint stage runs end to end on the CPU backend:
    save MB/s as the headline rate plus restore timing, bytes, and
    chunk/file counts in the stage detail — tier-1 guards the stage
    plumbing (Checkpointer → manifest → resharding restore) without a
    chip."""
    env = dict(os.environ)
    env["BENCH_FORCE_CPU"] = "1"
    env["BENCH_FAST"] = "1"
    env["BENCH_BUDGET_SEC"] = "150"
    env["BENCH_ONLY"] = "ckpt"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=200, cwd=REPO, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    det = json.loads(out.stdout.strip().splitlines()[-1])["detail"]
    assert det.get("ckpt_save_mb_per_sec"), det.get("ckpt_status")
    stage_detail = det.get("ckpt_detail", {})
    assert stage_detail.get("save_ms", 0) > 0
    assert stage_detail.get("restore_ms", 0) > 0
    assert stage_detail.get("mb", 0) > 0
    assert stage_detail.get("chunks", 0) > 0
    assert stage_detail.get("shard_files", 0) >= 1
    assert stage_detail.get("restore_mb_per_sec", 0) > 0


def test_bench_moe_and_word2vec_sharded_stages_on_cpu():
    """The grouped-MoE dispatch A/B stage and the mesh-sharded word2vec
    stage run end to end on the CPU backend (8 faked devices): the moe
    detail blob carries every (impl, G) config with tokens/s + estimated
    comm bytes + capacity + drop fraction and the A/B ratios, and the
    sharded word2vec stage lands a words/s number — tier-1 guards the new
    stage plumbing without a chip."""
    env = dict(os.environ)
    env["BENCH_FORCE_CPU"] = "1"
    env["BENCH_FAST"] = "1"
    env["BENCH_BUDGET_SEC"] = "240"
    env["BENCH_ONLY"] = "moe,word2vec_sharded"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=300, cwd=REPO, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    det = json.loads(out.stdout.strip().splitlines()[-1])["detail"]
    assert det.get("moe_tokens_per_sec"), det.get("moe_status")
    blob = det.get("moe_detail", {})
    assert blob.get("mesh", {}).get("expert", 0) >= 2
    assert blob.get("top_k") == 2
    for group in (1, 4):
        for impl in ("alltoall", "replicated"):
            cfg = blob.get(f"{impl}_g{group}", {})
            assert cfg.get("tokens_per_sec", 0) > 0, (impl, group, blob)
            assert cfg.get("est_fwd_comm_bytes_per_dev", 0) > 0
            assert cfg.get("capacity", 0) > 0
            assert cfg.get("dropped_frac") is not None
        # G experts per device actually materialized: E = G × ep
        assert blob[f"alltoall_g{group}"]["n_experts"] == group * \
            blob["mesh"]["expert"]
        assert f"alltoall_vs_replicated_g{group}" in blob
    assert "comm_model" in blob
    # the headline value is the alltoall G=4 rate
    assert det["moe_tokens_per_sec"] == blob["alltoall_g4"]["tokens_per_sec"]
    assert det.get("word2vec_sharded_words_per_sec"), det.get(
        "word2vec_sharded_status")


def test_bench_skips_stages_past_deadline():
    env = dict(os.environ)
    env["BENCH_FORCE_CPU"] = "1"
    env["BENCH_FAST"] = "1"
    env["BENCH_BUDGET_SEC"] = "1"  # already expired: every stage must skip
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=120, cwd=REPO, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["value"] == 0.0 and rec["vs_baseline"] is None
    assert all(
        v == "skipped_budget"
        for k, v in rec["detail"].items() if k.endswith("_status")
    )


def test_bench_fault_tolerance_stages_on_cpu():
    """The ISSUE-6 robustness stages run end to end on the CPU backend:
    ``ckpt_async`` reports save-step jitter for blocking vs background
    snapshots (background overhead must not exceed blocking — the whole
    point of the writer thread), and ``elastic_sync`` reports the SparkNet
    sync-period A/B (held-out loss + steps/s for sync_every ∈ {1,8,32})."""
    env = dict(os.environ)
    env["BENCH_FORCE_CPU"] = "1"
    env["BENCH_FAST"] = "1"
    env["BENCH_BUDGET_SEC"] = "300"
    env["BENCH_ONLY"] = "ckpt_async,elastic_sync"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=360, cwd=REPO, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    det = json.loads(out.stdout.strip().splitlines()[-1])["detail"]

    assert det.get("ckpt_async_blocking_vs_background"), det.get(
        "ckpt_async_status")
    ca = det.get("ckpt_async_detail", {})
    assert ca["blocking"]["save_step_ms"] > 0
    assert ca["background"]["plain_step_ms"] > 0
    # the background writer must take (at least) no MORE off the training
    # thread than a blocking save; on any real disk it takes far less
    assert (ca["background"]["save_overhead_ms"]
            <= ca["blocking"]["save_overhead_ms"] + 1.0), ca

    assert det.get("elastic_sync_steps_per_sec"), det.get(
        "elastic_sync_status")
    es = det.get("elastic_sync_detail", {})
    per = es["per_sync_every"]
    assert set(per) == {"1", "8", "32"}
    for cfg in per.values():
        assert cfg["final_eval_loss"] > 0
        assert cfg["steps_per_sec"] > 0
    # infrequent sync is faster wall-clock (fewer averaging barriers)
    assert per["32"]["steps_per_sec"] >= per["1"]["steps_per_sec"], per


def test_bench_elastic_trace_stage_on_cpu():
    """ISSUE 7 acceptance: the traced elastic round stays under the <5%
    overhead budget vs untraced (round-alternating paired estimator, same
    discipline as the PR 2 metrics budget), and the stage's forensic
    chain lands: spans on disk, a trace_report timeline with every round
    committed, a Chrome export, and a flight dump.

    The estimator's documented noise floor on a shared-CPU box is ~±1.5%
    (trimmed mean of 20 paired deltas; see measure_elastic_trace), so a
    single reading can graze the budget on a bad scheduler day — one
    retry keeps the gate honest (a REAL regression, like per-poll spans
    or uncapped dumps, measures 10-20% and fails both runs)."""

    def run_stage():
        env = dict(os.environ)
        env["BENCH_FORCE_CPU"] = "1"
        env["BENCH_FAST"] = "1"
        env["BENCH_BUDGET_SEC"] = "200"
        env["BENCH_ONLY"] = "elastic_trace"
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py")],
            capture_output=True, text=True, timeout=260, cwd=REPO, env=env,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        det = json.loads(out.stdout.strip().splitlines()[-1])["detail"]
        assert det.get("elastic_trace_overhead_pct") is not None, det.get(
            "elastic_trace_status")
        return det

    det = run_stage()
    sd = det["elastic_trace_detail"]
    # forensic chain (stable, no retry needed)
    assert sd["spans"] > 10
    assert sd["rounds_committed_in_report"] == 4
    assert sd["chrome_events"] > sd["spans"]  # spans + process metadata
    assert sd["flight_dump"] is True
    assert sd["plain_round_ms"] > 0 and sd["traced_round_ms"] > 0
    if sd["overhead_pct"] >= 5.0:  # noise-floor retry, see docstring
        sd = run_stage()["elastic_trace_detail"]
    assert sd["overhead_pct"] < 5.0, sd


def test_bench_guardrails_stage_on_cpu():
    """ISSUE 8 acceptance: the guarded composed-LM step costs <5% vs the
    identical unguarded step (paired-median estimator, same discipline as
    the telemetry/trace budgets), and the stage's recovery demo lands end
    to end — an injected NaN batch skipped in-graph (skipped_steps==1,
    params carried bitwise and finite), the faulting step dumped as a
    replay bundle, and tools/step_replay.py reproducing the non-finite
    result from it.

    The overhead estimator shares the shared-CPU noise floor of the other
    A/B stages (~±2% on a bad scheduler day) — one retry keeps the gate
    honest; a real regression (e.g. a host sync inside the guard) measures
    far above 5% on both runs."""

    def run_stage():
        env = dict(os.environ)
        env["BENCH_FORCE_CPU"] = "1"
        env["BENCH_FAST"] = "1"
        env["BENCH_BUDGET_SEC"] = "300"
        env["BENCH_ONLY"] = "guardrails"
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py")],
            capture_output=True, text=True, timeout=360, cwd=REPO, env=env,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        det = json.loads(out.stdout.strip().splitlines()[-1])["detail"]
        assert det.get("guardrails_overhead_pct") is not None, det.get(
            "guardrails_status")
        return det

    det = run_stage()
    sd = det["guardrails_detail"]
    # recovery demo (stable, no retry needed)
    rec = sd["recovery"]
    assert rec["skipped_steps"] == 1
    assert rec["params_carried_bitwise"] is True
    assert rec["params_finite_after_skip"] is True
    assert rec["replay_rc"] == 0
    assert rec["replay_reproduced"] is True
    assert rec["poisoned_leaves"] == ["['batch']['x']"]
    import math
    assert math.isfinite(rec["post_recovery_loss"])
    if sd["overhead_pct"] >= 5.0:  # noise-floor retry, see docstring
        sd = run_stage()["guardrails_detail"]
    assert sd["overhead_pct"] < 5.0, sd


def test_bench_profile_stage_on_cpu():
    """ISSUE 9 acceptance: the ``profile=`` seam is COMPILE-TIME-ONLY —
    the profiled composed-LM step (AOT lower/compile once, then the same
    executable every call) must cost <5% vs the identical plain jitted
    step in steady state, and the stage's StepProfile blob must land with
    non-null FLOPs, the analytic-vs-XLA cross-check inside the documented
    band, a roofline attribution, and an explicit (empty-on-CPU)
    watermark block.

    Same shared-CPU noise floor as the other A/B budget stages (~±2% on
    a bad scheduler day) — one retry keeps the gate honest; a real
    regression (e.g. re-profiling per call) measures far above 5% on
    both runs."""

    def run_stage():
        env = dict(os.environ)
        env["BENCH_FORCE_CPU"] = "1"
        env["BENCH_FAST"] = "1"
        env["BENCH_BUDGET_SEC"] = "240"
        env["BENCH_ONLY"] = "profile"
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py")],
            capture_output=True, text=True, timeout=300, cwd=REPO, env=env,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        det = json.loads(out.stdout.strip().splitlines()[-1])["detail"]
        assert det.get("profile_overhead_pct") is not None, det.get(
            "profile_status")
        return det

    det = run_stage()
    sd = det["profile_detail"]
    blob = sd["profile"]
    assert blob["label"] == "lm_single_device" and blob["platform"] == "cpu"
    assert blob["flops"] > 0 and blob["bytes_accessed"] > 0
    assert blob["donated_args"] >= 1
    assert blob["compile_seconds"] > 0
    assert blob["collectives"] == {}  # single device: no comm
    # the analytic cross-check: the scan-adjusted XLA expectation holds
    # (the full-table ratio is also recorded for context)
    assert 0.85 <= sd["xla_vs_analytic_flops"] <= 1.25, sd
    assert sd["analytic_train_flops"] > 0
    assert sd["attribution"]["bound"] in ("compute", "memory", "comm")
    assert sd["signature_fallbacks"] == 0
    # the watermark sampler ran; CPU reports no per-device stats, and the
    # stage says so explicitly instead of inventing numbers
    assert sd["memory_watermarks"]["samples"] > 0
    assert sd["memory_watermarks"]["devices"] == {}
    if sd["overhead_pct"] >= 5.0:  # noise-floor retry, see docstring
        sd = run_stage()["profile_detail"]
    assert sd["overhead_pct"] < 5.0, sd


def test_bench_serve_stage_on_cpu():
    """ISSUE 10 acceptance: the serve stage runs end to end on the CPU
    backend — the continuous-batching decode engine beats the naive
    recompute-per-token baseline on tokens/s (same bf16 weights, so the
    ratio isolates the KV cache + batching), p50/p95 latency lands under
    the open-loop traffic generator, every request completes, and the
    int8 weight-only twin reports its smaller at-rest footprint.

    The throughput ratio shares the shared-CPU noise floor of the other
    A/B stages — one retry keeps the gate honest (the measured margin is
    ~2x; a real regression, like a retrace per occupancy change, lands
    well under 1.0 on both runs)."""

    def run_stage():
        env = dict(os.environ)
        env["BENCH_FORCE_CPU"] = "1"
        env["BENCH_FAST"] = "1"
        env["BENCH_BUDGET_SEC"] = "360"  # watch twins: 12 paired runs
        env["BENCH_ONLY"] = "serve"
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py")],
            capture_output=True, text=True, timeout=420, cwd=REPO, env=env,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        det = json.loads(out.stdout.strip().splitlines()[-1])["detail"]
        assert det.get("serve_tokens_per_sec"), det.get("serve_status")
        return det

    det = run_stage()
    sd = det["serve_detail"]
    # stable structure (no retry needed)
    assert det["serve_tokens_per_sec"] == sd["tokens_per_sec"]
    assert sd["completed"] == sd["n_requests"]
    lat = sd["latency"]
    assert lat["p99_ms"] >= lat["p95_ms"] >= lat["p50_ms"] > 0
    assert lat["mean_ms"] > 0
    assert lat["first_token_p99_ms"] >= lat["first_token_p50_ms"] > 0
    assert sd["naive_tokens_per_sec"] > 0
    assert sd["occupancy_mean"] > 0
    assert sd["serve_dtype"] == "bf16"
    # goodput under SLO (ISSUE 15 satellite): reported alongside the
    # percentiles and coherent with them — attainment is a fraction and
    # goodput can never exceed completed/duration
    gp = sd["goodput"]
    assert gp["slo_ms"] > 0
    assert 0.0 <= gp["slo_attainment"] <= 1.0
    assert gp["goodput_rps"] >= 0.0
    assert gp["goodput_rps"] <= sd["completed"] / max(
        sd["latency"]["p50_ms"] / 1000.0, 1e-9)
    # lockwatch twin (ISSUE 11): the watched run stays cycle-free and
    # inside the <5% tokens/s budget (shared-CPU noise: one retry below
    # rides the serve_vs_naive retry)
    watch = sd["lockwatch"]
    assert watch["cycles"] == 0 and watch["watchdog_dumps"] == 0
    assert watch["engine_lock"].get("acquires", 0) > 0
    assert watch["metrics"].get("lockwatch_serve_engine_acquires", 0) > 0
    # int8 A/B twin: decodes, and the at-rest weights really shrank
    assert sd["int8"]["tokens_per_sec"] > 0
    assert sd["int8"]["weight_bytes"] < sd["weight_bytes"]
    assert sd["int8"]["weight_bytes_vs_bf16"] < 1.0
    # tracing twin (ISSUE 12): every open-loop request reconstructed by
    # the REAL tools/trace_report.py attribution with queue+prefill+
    # decode+gap summing to the request latency within 1ms (stable
    # structure; the overhead budget shares the noise retry below)
    tw = sd["tracing"]
    assert tw["requests_traced"] >= sd["n_requests"]
    assert tw["open_requests"] == 0
    assert tw["attribution_max_err_ms"] is not None
    assert tw["attribution_max_err_ms"] <= 1.0, tw
    assert tw["sample_attribution"]["status"] == "ok"
    # netwatch twin (ISSUE 18): arming the socket watchdog around the
    # same open-loop run is free for the decode hot path (budget shares
    # the noise retry below), and the in-window tracker RPC roundtrip
    # exercised the seam end to end — both the client socket and the
    # server handler socket show live per-endpoint counters, with no
    # stall dumps on a healthy run
    nw = sd["netwatch"]
    assert nw["stall_dumps"] == 0, nw
    assert nw["default_timeout_s"] > 0
    assert nw["endpoints"].get("tracker.client", {}).get("ops", 0) > 0, nw
    assert nw["endpoints"].get(
        "tracker.server.handler", {}).get("ops", 0) > 0, nw
    assert nw["endpoints"]["tracker.client"]["timeouts"] == 0, nw
    assert nw["metrics"].get("netwatch_tracker_client_ops", 0) > 0, nw
    # the acceptance ratios: continuous batching beats recompute-per-token
    # AND the armed watchdog AND the armed socket watch each cost <5%
    # tokens/s; the armed tracer gets a 10% fast-mode budget — its eager
    # line-buffered JSONL sink is a real fixed per-span cost that these
    # ~0.1s micro-runs can't amortize (the same-engine paired estimator
    # in bench.py measures it at ~5%, reliably, where the old
    # single-shot estimator hid it in ±10% run noise). One shared noise
    # retry.
    if (sd["serve_vs_naive"] <= 1.0
            or sd["lockwatch"]["overhead_pct"] >= 5.0
            or sd["tracing"]["overhead_pct"] >= 10.0
            or sd["netwatch"]["overhead_pct"] >= 5.0):
        sd = run_stage()["serve_detail"]
    assert sd["serve_vs_naive"] > 1.0, sd
    assert sd["lockwatch"]["overhead_pct"] < 5.0, sd["lockwatch"]
    assert sd["tracing"]["overhead_pct"] < 10.0, sd["tracing"]
    assert sd["netwatch"]["overhead_pct"] < 5.0, sd["netwatch"]


def test_bench_fleet_stage_on_cpu():
    """ISSUE 19 acceptance: the fleet stage runs end to end on the CPU
    backend — two real FleetReplica serve/heartbeat loops over the TCP
    tracker, open-loop traffic routed with session affinity (latency +
    goodput blocks land for the fleet_* bench_report rows), then a
    mid-stream replica kill: the router detects the death off heartbeat
    staleness, requeues every in-flight request, cold-starts a
    replacement from live params, and every accepted request completes
    token-identical to the single-engine oracle. The requeue block
    carries the recovery-latency number the LOWER-IS-BETTER
    fleet_requeue_to_first_token_ms row tracks."""
    env = dict(os.environ)
    env["BENCH_FORCE_CPU"] = "1"
    env["BENCH_FAST"] = "1"
    env["BENCH_BUDGET_SEC"] = "300"
    env["BENCH_ONLY"] = "fleet"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=360, cwd=REPO, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    det = json.loads(out.stdout.strip().splitlines()[-1])["detail"]
    assert det.get("fleet_tokens_per_sec"), det.get("fleet_status")
    sd = det["fleet_detail"]
    assert det["fleet_tokens_per_sec"] == sd["tokens_per_sec"]
    # healthy phase: full membership, every request completed, latency/
    # goodput coherent (these blocks feed the bench_report extractors)
    assert sd["completed"] == sd["n_requests"]
    assert sd["replicas"] == 2
    lat = sd["latency"]
    assert lat["p99_ms"] >= lat["p95_ms"] >= lat["p50_ms"] > 0
    assert lat["first_token_p99_ms"] >= lat["first_token_p50_ms"] > 0
    gp = sd["goodput"]
    assert gp["slo_ms"] > 0
    assert 0.0 <= gp["slo_attainment"] <= 1.0
    assert gp["goodput_rps"] >= 0.0
    healthy = sd["healthy"]
    assert healthy["alive"] == 2
    assert healthy["affinity_sessions"] >= 1
    assert sum(healthy["dispatches"].values()) >= sd["n_requests"]
    # chaos phase: the kill really fired mid-stream, every accepted
    # request still completed, outputs pinned to the oracle, the dead
    # replica buried and its replacement alive in the final snapshot
    chaos = sd["chaos"]
    assert chaos["kill_fired"] is True
    assert chaos["completed"] == chaos["n_requests"]
    assert chaos["token_identical"] is True
    assert chaos["failed_replicas"] == ["r1"]
    assert chaos["replacement_joined"] is True
    assert chaos["requeued_requests"] >= 1
    rq = sd["requeue"]
    assert rq["requeued_requests"] >= 1
    assert rq["requeue_to_first_token_ms"] > 0
    assert rq["requeue_to_first_token_max_ms"] >= \
        rq["requeue_to_first_token_ms"]


def test_bench_observability_stage_on_cpu():
    """ISSUE 15 acceptance: the observability stage runs end to end on
    the CPU backend — the SAME open-loop serve run with the watch layer
    armed (history sampler at 20Hz + alert engine on the default pack at
    10Hz) costs <5% tokens/s (the shared noise retry keeps the gate
    honest on a loaded box), the quiet run fires NOTHING, the armed
    run's history answers live rate/percentile queries, and the
    deterministic injected-fault demo drives nonfinite_step_rate AND
    serve_latency_slo_burn to firing with the transitions rendered
    through the REAL tools/alert_report.py."""

    def run_stage():
        env = dict(os.environ)
        env["BENCH_FORCE_CPU"] = "1"
        env["BENCH_FAST"] = "1"
        env["BENCH_BUDGET_SEC"] = "240"
        env["BENCH_ONLY"] = "observability"
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py")],
            capture_output=True, text=True, timeout=300, cwd=REPO, env=env,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        det = json.loads(out.stdout.strip().splitlines()[-1])["detail"]
        assert det.get("observability_overhead_pct") is not None, det.get(
            "observability_status")
        return det["observability_detail"]

    sd = run_stage()
    # stable structure (no retry needed)
    assert sd["tokens_per_sec"] > 0
    assert sd["tokens_per_sec_watched"] > 0
    hist = sd["history"]
    assert hist["samples"] >= 2          # sampler really ran
    assert hist["series"] > 0
    assert hist["serve_tokens_rate_per_s"] > 0   # live rate query worked
    al = sd["alerts"]
    assert al["rules"] == 16  # default pack incl. ISSUE 16 serve rules
    # + the ISSUE 17 runprof rules + the ISSUE 19 fleet rules
    # + the ISSUE 20 tune_cache_stale rule
    # a healthy run pages nobody
    assert al["quiet_run_firing"] == []
    # the injected-fault demo fired BOTH demo rules deterministically...
    assert al["demo_states"] == {"nonfinite_step_rate": "firing",
                                 "serve_latency_slo_burn": "firing"}
    # ...and the real alert_report rendered the transitions
    assert al["report_transitions"] >= 2
    assert al["report_fired"] == ["nonfinite_step_rate",
                                  "serve_latency_slo_burn"]
    # the armed-watch overhead budget, with the shared noise retry
    if sd["overhead_pct"] >= 5.0:  # noise-floor retry, see docstring
        sd = run_stage()
    assert sd["overhead_pct"] < 5.0, sd


def test_bench_runprof_stage_on_cpu():
    """ISSUE 17 acceptance: the runprof stage runs end to end on the CPU
    backend — the SAME open-loop serve run with the runprof seam timing
    every scheduler tick costs <5% tokens/s (shared noise retry), the
    armed run's streaming gauges carry real values, the composed-LM
    measured-MFU cross-check holds (runprof_measured_mfu — fenced device
    seconds — is >= the wall-clock MFU, within the documented band the
    tier-1 test pins), and the N-step capture session round-trips
    through load_session + the profile_report runtime renderer."""

    def run_stage():
        env = dict(os.environ)
        env["BENCH_FORCE_CPU"] = "1"
        env["BENCH_FAST"] = "1"
        env["BENCH_BUDGET_SEC"] = "240"
        env["BENCH_ONLY"] = "runprof"
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py")],
            capture_output=True, text=True, timeout=300, cwd=REPO, env=env,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        det = json.loads(out.stdout.strip().splitlines()[-1])["detail"]
        assert det.get("runprof_overhead_pct") is not None, det.get(
            "runprof_status")
        # the cross-check MFU is lifted to its own tracked row
        assert det.get("runprof_measured_mfu") is not None
        return det["runprof_detail"]

    sd = run_stage()
    # stable structure (no retry needed)
    assert sd["tokens_per_sec"] > 0
    assert sd["tokens_per_sec_runprof"] > 0
    g = sd["serve_gauges"]
    assert g["runprof_steps_total"] > 0      # ticks really flushed
    assert g["runprof_step_ms"] > 0
    assert g["runprof_steps_per_s"] > 0
    # the measured-MFU cross-check: fenced device wall <= wall clock,
    # so measured >= wall; and both are real nonzero numbers
    assert sd["measured_mfu"] > 0
    assert sd["wall_mfu"] > 0
    assert sd["measured_vs_wall_mfu"] >= 1.0, sd
    # session -> report chain
    sess = sd["session"]
    assert sess["steps"] == sd["lm_steps"]
    assert sess["partial"] is False
    assert sess["chrome_events"] > 0
    assert sess["session_mfu"] > 0
    assert sess["report_rendered"] is True
    # the armed-seam overhead budget, with the shared noise retry
    if sd["overhead_pct"] >= 5.0:  # noise-floor retry, see docstring
        sd = run_stage()
    assert sd["overhead_pct"] < 5.0, sd


def test_bench_autotune_stage_on_cpu():
    """ISSUE 20 acceptance: the autotune stage runs the REAL two-phase
    roofline search end to end on the CPU backend — the LM seam's
    candidates flow through make_single_device_train_step(tuned=cfg),
    the serve seam through profiled prefill/KV shapes + a live engine —
    and the headline tuned-vs-default ratio lands >= 1.0 (the default is
    always a candidate, so the stage can never report a regression;
    within-noise margins are informational-marked, never claimed)."""
    env = dict(os.environ)
    env["BENCH_FORCE_CPU"] = "1"
    env["BENCH_FAST"] = "1"
    env["BENCH_BUDGET_SEC"] = "420"
    env["BENCH_ONLY"] = "autotune"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=480, cwd=REPO, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    det = json.loads(out.stdout.strip().splitlines()[-1])["detail"]
    ratio = det.get("autotune_tuned_vs_default")
    assert ratio is not None, det.get("autotune_status")
    assert ratio >= 1.0, ratio  # default always a candidate
    sd = det["autotune_detail"]
    # both searched seams landed with a full count ledger
    for seam in ("flash_attention", "serve"):
        s = sd["seams"][seam]
        assert s["tuned_vs_default"] >= 1.0, (seam, s)
        c = s["counts"]
        assert c["total"] == c["invalid"] + c["profiled"]  # all accounted
        assert c["measured"] >= 1                    # frontier executed
        assert c["pruned"] <= c["profiled"]          # pruning from phase 1
        assert s["winner"] is not None and s["default"] is not None
    # the serve seam's ratio is lifted to its own tracked row
    assert det.get("autotune_serve_tuned_vs_default") == \
        sd["seams"]["serve"]["tuned_vs_default"]
    # the informational noise marker is present either way
    assert "headline_within_noise" in sd


def test_bench_comm_overlap_stage_on_cpu():
    """ISSUE 14 acceptance: the comm_overlap stage runs end to end on the
    CPU backend (8 faked devices) — the 2D-factorized MoE dispatch lands
    with twice the all_to_all definitions at half the group size and loss
    parity vs flat, the overlapped pipeline and prefetch-ring twins are
    BIT-identical to their strict oracles, every config carries a measured
    comm fraction, and the counted-configs gate is honest (CPU collectives
    are memcpys, so the stage must MARK configs informational rather than
    claim wins). No timing-ratio assertion: the schedules' wall-clock win
    needs real ICI; the correctness+shape+gating chain is what tier-1
    pins."""
    env = dict(os.environ)
    env["BENCH_FORCE_CPU"] = "1"
    env["BENCH_FAST"] = "1"
    env["BENCH_BUDGET_SEC"] = "300"
    env["BENCH_ONLY"] = "comm_overlap"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=360, cwd=REPO, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    det = json.loads(out.stdout.strip().splitlines()[-1])["detail"]
    assert det.get("comm_overlap_overlap_vs_strict"), det.get(
        "comm_overlap_status")
    sd = det["comm_overlap_detail"]

    # (1) the 2D factorization: two group-factorized a2a definitions per
    # flat one, strictly smaller replica groups, exact loss parity
    a2a = sd["a2a"]
    assert a2a["grid"] == [2, 2]
    assert a2a["alltoall"]["a2a_group_sizes"] == [4]
    assert a2a["alltoall_2d"]["a2a_group_sizes"] == [2]
    assert a2a["alltoall_2d"]["a2a_count"] == 2 * a2a["alltoall"]["a2a_count"]
    assert a2a["parity_loss_abs_diff"] <= 1e-5
    assert a2a["alltoall"]["step_ms"] > 0
    assert a2a["alltoall_2d"]["step_ms"] > 0
    assert "2d_vs_flat" in a2a

    # (2) overlapped pipeline: bit-identical to strict
    pp = sd["pipeline"]
    assert pp["bit_identical"] is True
    assert pp["strict"]["collective_permute_count"] >= 1
    assert pp["overlap_vs_strict"] > 0

    # (3) prefetch ring: bit-identical to rotate-after-attend
    ring = sd["ring"]
    assert ring["bit_identical"] is True
    assert ring["prefetch_vs_rotate_after"] > 0

    # comm-fraction gating present and honest on CPU
    for cfg, key in (("a2a", "alltoall"), ("pipeline", "strict"),
                     ("ring", "rotate_after")):
        assert sd[cfg][key]["comm_fraction"] >= 0
    assert isinstance(sd["counted_configs"], list)
    assert isinstance(sd["headline_counted"], bool)

    # tracked blob + wire row: the 2D dispatch profile embeds
    blob = sd["profile"]
    assert blob["label"] == "comm_overlap_alltoall_2d"
    assert blob["collectives"]["all-to-all"]["group_sizes"] == [2]
    assert sd["collective_wire_bytes"] == blob["collective_wire_bytes"]
    # lifted ratio rows for bench_report tracking
    assert det["comm_overlap_a2a_2d_vs_flat"] == a2a["2d_vs_flat"]
    assert det["comm_overlap_ring_prefetch_vs_rotate_after"] == \
        ring["prefetch_vs_rotate_after"]


def test_bench_optimizer_stage_on_cpu():
    """ISSUE 13 acceptance: the in-graph optimizer A/B stage runs end to
    end on the CPU backend (8 faked devices, dp×ep mesh) — SGD vs
    Adam(replicated) vs Adam/LAMB(update-sharded) all land steps/s plus
    compiled StepProfile footprints, the headline replicated/sharded
    peak-bytes ratio is STRICTLY > 1 (the ZeRO-sharded update's compiled
    footprint is smaller — this is the profiler-provable claim, not a
    timing race, so no noise retry is needed), the measured per-replica
    moment bytes shrink by exactly the dp factor, the sharded Adam blob
    (the bench_report ``optimizer_profile_peak_bytes`` LOWER-IS-BETTER
    row) embeds as the stage profile with the params all-gather in its
    collective inventory, and the sharded-vs-replicated parity check at
    identical math stays ≤1e-5 at bench shapes."""
    env = dict(os.environ)
    env["BENCH_FORCE_CPU"] = "1"
    env["BENCH_FAST"] = "1"
    env["BENCH_BUDGET_SEC"] = "300"
    env["BENCH_ONLY"] = "optimizer"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=360, cwd=REPO, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    det = json.loads(out.stdout.strip().splitlines()[-1])["detail"]
    ratio = det.get("optimizer_peak_bytes_ratio")
    assert ratio, det.get("optimizer_status")
    assert ratio > 1.0, det
    sd = det["optimizer_detail"]
    dp = sd["mesh"]["data"]
    assert dp >= 2 and sd["mesh"]["expert"] >= 2
    for cfg in ("sgd", "adam_replicated", "adam_sharded", "lamb_sharded"):
        blob = sd[cfg]
        assert blob["steps_per_sec"] > 0, (cfg, blob)
        assert blob["profile_peak_bytes"] > 0
        assert blob["profile_flops"] > 0
    # the footprint claim, per config: sharded < replicated on BOTH the
    # compiled peak and the at-rest per-replica moment bytes (the latter
    # by exactly the dp factor — no padding slack at bench shapes)
    assert (sd["adam_sharded"]["profile_peak_bytes"]
            < sd["adam_replicated"]["profile_peak_bytes"])
    assert (sd["adam_sharded"]["moment_bytes_per_replica"]
            < sd["adam_replicated"]["moment_bytes_per_replica"])
    assert sd["moment_bytes_ratio"] == float(dp)
    # the redundant-update FLOPs drop (per-replica program)
    assert (sd["adam_sharded"]["profile_flops"]
            < sd["adam_replicated"]["profile_flops"])
    # the tracked blob is the sharded Adam step, all-gather present
    assert sd["profile"]["label"] == "optimizer_adam_sharded"
    assert "all-gather" in sd["profile"]["collectives"]
    assert "all-gather" in sd["adam_sharded"]["collectives"]
    # identical math: sharded and replicated agree after 3 steps
    assert sd["adam_sharded_vs_replicated_parity_max_abs_diff"] <= 1e-5
    assert sd["adam_loss_delta"] <= 1e-5


def test_bench_ref_micro_stage_on_cpu():
    """ISSUE 16: the machine-noise reference stage runs end to end on the
    CPU backend and reports a positive rate under the standard
    samples_per_sec key — tools/bench_report.py keys its round-over-round
    normalization off this row, so the stage silently dying would turn
    every future delta back into raw (unnormalized) noise."""
    env = dict(os.environ)
    env["BENCH_FORCE_CPU"] = "1"
    env["BENCH_FAST"] = "1"
    env["BENCH_BUDGET_SEC"] = "60"
    env["BENCH_ONLY"] = "ref_micro"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=120, cwd=REPO, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    det = json.loads(out.stdout.strip().splitlines()[-1])["detail"]
    assert det.get("ref_micro_samples_per_sec", 0) > 0, det.get(
        "ref_micro_status")


# ------------------------------------------------ stage-coverage meta-test ----

# Stages that predate this meta-test and whose plumbing is the ONE shared
# measure()/measure_word2vec() code path — it is exercised by the
# mlp/lenet smokes above (same _conf/_make_data/measure machinery, only
# the model/precision params differ), and the skip test runs every stage
# through the budget discipline. A NEW stage with new plumbing must NOT
# be added here: give it a BENCH_ONLY smoke like the ones above.
_LEGACY_MEASURE_STAGES = {
    "mlp_fp32_true", "conv_wide_bf16", "conv_wide_bf16_im2col",
    "lstm_bf16", "lstm_fp32", "lstm_wide_bf16", "lstm_wide_bf16_nokernels",
    "attn_bf16", "attn_long_bf16", "attn_long_bf16_densecore",
    "cpu_word2vec", "word2vec", "cpu_word2vec_large", "word2vec_large",
}


def _smoked_stages():
    """Every stage named in a BENCH_ONLY assignment in THIS file — the
    stages with a dedicated end-to-end smoke."""
    import re

    src = open(os.path.abspath(__file__)).read()
    covered = set()
    for m in re.finditer(r'env\["BENCH_ONLY"\]\s*=\s*\(?([^\n]+)', src):
        # the assignment may be a parenthesized multi-line string concat
        chunk = src[m.start():m.start() + 400]
        for lit in re.findall(r'"([^"]+)"', chunk.split("out = ")[0]):
            if lit == "BENCH_ONLY":
                continue
            covered.update(s.strip() for s in lit.split(",") if s.strip())
    return covered


def test_every_bench_stage_has_smoke():
    """ISSUE 8 satellite: every bench.py stage is either smoked by a
    BENCH_ONLY test in this file or explicitly allowlisted as a legacy
    measure()-family stage — a future stage cannot land without tier-1
    coverage of its plumbing. The allowlist itself is pinned against the
    live STAGES list so it can only ever shrink honestly."""
    sys.path.insert(0, REPO)
    import bench

    stages = {name for name, _cap in bench.STAGES}
    covered = _smoked_stages()
    missing = sorted(stages - covered - _LEGACY_MEASURE_STAGES)
    assert not missing, (
        f"bench stages without a smoke test: {missing} — add a BENCH_ONLY "
        "smoke in tests/test_bench_smoke.py (see the guardrails stage's) "
        "or, ONLY for a measure()-family variant, extend "
        "_LEGACY_MEASURE_STAGES with a why")
    stale = sorted(_LEGACY_MEASURE_STAGES - stages)
    assert not stale, f"allowlisted stages no longer exist: {stale}"
    # the newer stages really are covered by dedicated smokes
    assert "guardrails" in covered
    assert "profile" in covered
