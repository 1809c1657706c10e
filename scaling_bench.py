"""Scaling-efficiency benchmark with collective-vs-compute breakdown
(BASELINE config #5 analogue).

Measures the synchronous data-parallel training step (in-graph gradient
AllReduce — the XLA-native rewrite of the reference's per-iteration
ParameterAveraging loop, ref: spark/impl/multilayer/SparkDl4jMultiLayer.java:183-203)
at 1/2/4/8 virtual CPU devices, fixed per-device batch (weak scaling).

Three timings per device count n (global batch = 256·n):
  dp_ms      — the real sharded step (compute + sharding machinery + psum)
  ablated_ms — the SAME sharded step with the psum replaced by identity
               (trainer.make_sync_train_step(ablate_collectives=True)):
               identical compute and sharding machinery, no collective
  single_ms  — the same global batch as ONE un-sharded step on 1 device:
               identical total FLOPs on identical silicon

Decomposition:
  collective_ms    = dp_ms − ablated_ms     (the AllReduce itself)
  mesh_overhead_ms = ablated_ms − single_ms (virtual-mesh artifact: n
                     per-shard executions dispatched onto the SAME host
                     core(s), losing the one-big-matmul batching the single
                     -device run gets — this term does not exist on real
                     chips, where each shard owns its silicon)
  dp_overhead_efficiency   = single_ms / dp_ms   (the honest virtual-mesh
                             number; ideal 1.0)
  collective_only_efficiency = single_ms / (single_ms + collective_ms)
                             (what remains once each shard owns its compute
                             — the framework-attributable share)

Virtual CPU "devices" share the host's core(s) (`nproc` is recorded in the
artifact), so wall-clock cannot weak-scale here; the reference's own test
posture has the same property (Spark local[8] on one socket).

Run:  python scaling_bench.py  →  prints JSON and writes SCALING_r05.json
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

PER_DEVICE_BATCH = 256
STEPS = 30
WARMUP = 5
REPEATS = 3
OUT = "SCALING_r05.json"

REPO = os.path.dirname(os.path.abspath(__file__))


def _child_main(n: int, batch: int, mode: str, warmup: int = WARMUP,
                steps: int = STEPS, repeats: int = REPEATS) -> None:
    """One measurement child: runs in a FRESH subprocess (the virtual CPU
    device count is fixed at backend init) and prints one RES json line.

    A real function rather than a ``python -c`` template string so the
    graftlint untimed-dispatch rule can SEE the timed loops and keep the
    block_until_ready-before-clock-stop discipline enforced (the round-2
    enqueue-rate bug class)."""
    import json as _json
    import statistics
    import time

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n)
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.zoo import mnist_mlp
    from deeplearning4j_tpu.nn import functional as F
    from deeplearning4j_tpu.parallel.mesh import data_parallel_mesh
    from deeplearning4j_tpu.parallel.trainer import make_sync_train_step

    ablate = mode == "ablate"
    conf = mnist_mlp(256, 128)
    params = F.init_params(conf, jax.random.PRNGKey(0))
    states = F.init_train_state(conf, params)
    mesh = data_parallel_mesh(n)
    step = make_sync_train_step(conf, mesh, ablate_collectives=ablate)

    kx, ky = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.uniform(kx, (batch, 784), jnp.float32)
    y = jax.nn.one_hot(jax.random.randint(ky, (batch,), 0, 10), 10,
                       dtype=jnp.float32)
    w = jnp.ones((batch,), jnp.float32)
    key = jax.random.PRNGKey(1)

    # collective accounting via the shared compiled-step profiler (ISSUE 9;
    # replaces the ad-hoc as_text() scrape): one AOT compile, the inventory
    # counts sync AND async (-start) all-reduces with their analytic wire
    # bytes under the documented ring convention
    from deeplearning4j_tpu.telemetry.xprofile import profile_lowered

    prof = profile_lowered(
        step.lower(params, states, jnp.asarray(0), x, y, w, key),
        label=f"dp_sync[{n}]")
    allreduce = prof.collectives.get("all-reduce", {})
    n_allreduce = allreduce.get("count", 0)
    # ISSUE 14: also surface the all_to_all traffic so ep-axis scaling
    # runs capture the MoE dispatch cost (0 on the pure-dp step here)
    alltoall = prof.collectives.get("all-to-all", {})
    param_bytes = sum(int(jnp.size(leaf)) * 4 for layer in params
                      for leaf in jax.tree_util.tree_leaves(layer))

    # the same step key every iteration is deliberate: identical per-step
    # work across repeats is what makes the min/median spread meaningful
    for i in range(warmup):
        # graftlint: allow[prng-reuse] identical per-step randomness keeps repeat timings comparable
        params, states, score = step(params, states, jnp.asarray(i), x, y, w,
                                     key)
    jax.block_until_ready(params)
    # R repeats, ALL reported: a 1-core host makes single timings noisy under
    # transient background load. The minimum is the uncontended step time; the
    # parent records the min/median spread so subtraction-based attribution
    # can be flagged when it sits inside the repeat noise instead of silently
    # clamped (advisor r04).
    reps = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for i in range(steps):
            # graftlint: allow[prng-reuse] see the warmup loop above
            params, states, score = step(params, states, jnp.asarray(i), x, y,
                                         w, key)
        jax.block_until_ready(params)
        reps.append(time.perf_counter() - t0)
    assert bool(jnp.isfinite(score)), "non-finite score"
    print("RES", _json.dumps({
        "ms": min(reps) / steps * 1000.0,
        "ms_median": statistics.median(reps) / steps * 1000.0,
        "ms_repeats": [r / steps * 1000.0 for r in reps],
        "all_reduce_ops": n_allreduce,
        "all_reduce_wire_bytes": allreduce.get("wire_bytes", 0.0),
        "all_to_all_ops": alltoall.get("count", 0),
        "all_to_all_wire_bytes": alltoall.get("wire_bytes", 0.0),
        "xla_flops": prof.flops,
        "param_bytes": param_bytes,
    }), flush=True)


def measure(n_devices: int, global_batch: int, mode: str = "dp") -> dict:
    """Per-step stats at n virtual CPU devices (fresh subprocess — the
    device count is fixed at backend init). mode: dp | ablate."""
    code = (f"import sys; sys.path.insert(0, {REPO!r}); "
            f"from scaling_bench import _child_main; "
            f"_child_main({n_devices}, {global_batch}, {mode!r})")
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=600, cwd=REPO)
    for line in out.stdout.splitlines():
        if line.startswith("RES "):
            return json.loads(line[4:])
    raise RuntimeError(f"scaling child failed (n={n_devices}):\n{out.stderr[-2000:]}")


def main() -> None:
    nproc = os.cpu_count()
    rows = []
    param_bytes = None
    for n in (1, 2, 4, 8):
        gb = PER_DEVICE_BATCH * n
        dp = measure(n, gb, "dp")
        param_bytes = dp["param_bytes"]
        dp_ms = dp["ms"]
        if n == 1:
            abl = dp
            abl_ms = dp_ms
            single_ms = dp_ms
        else:
            abl = measure(n, gb, "ablate")
            abl_ms = abl["ms"]
            single_ms = measure(1, gb, "dp")["ms"]
        # collective_ms subtracts minima from two subprocesses; on a noisy
        # shared host the two minima can come from different contention
        # regimes. Record the raw (possibly negative) difference plus each
        # side's min→median spread, and flag the row when |diff| sits inside
        # that spread — never silently clamp (advisor r04).
        raw_diff = dp_ms - abl_ms
        spread = ((dp["ms_median"] - dp_ms) + (abl["ms_median"] - abl_ms))
        coll_ms = max(raw_diff, 0.0)
        rows.append({
            "devices": n,
            "per_device_batch": PER_DEVICE_BATCH,
            "global_batch": gb,
            "dp_step_ms": round(dp_ms, 3),
            "dp_step_ms_median": round(dp["ms_median"], 3),
            "ablated_step_ms": round(abl_ms, 3),
            "ablated_step_ms_median": round(abl["ms_median"], 3),
            "single_device_same_batch_ms": round(single_ms, 3),
            "collective_ms": round(coll_ms, 3),
            "collective_ms_raw_diff": round(raw_diff, 3),
            "collective_within_noise": bool(abs(raw_diff) <= spread),
            "repeat_spread_ms": round(spread, 3),
            "dp_step_ms_repeats": [round(r, 3) for r in dp["ms_repeats"]],
            "ablated_step_ms_repeats": [round(r, 3) for r in abl["ms_repeats"]],
            "mesh_overhead_ms": round(abl_ms - single_ms, 3),
            "dp_overhead_efficiency": round(single_ms / dp_ms, 3),
            "collective_only_efficiency": round(
                single_ms / (single_ms + coll_ms), 3),
            "all_reduce_ops_per_step": dp["all_reduce_ops"],
            "all_to_all_ops_per_step": dp["all_to_all_ops"],
            "all_to_all_wire_bytes_per_step": dp["all_to_all_wire_bytes"],
            "global_samples_per_sec": round(gb / (dp_ms / 1000.0), 1),
        })
    r8 = rows[-1]
    # ICI projection: one fused all-reduce of the grad pytree per step.
    # Ring all-reduce moves 2·(n−1)/n·payload per link; v5e ICI ≈ 45 GB/s
    # per direction per link, so the wire time at n=8 is ~tens of µs
    # against a per-shard compute of single_ms(256) — the measured
    # collective_ms here instead rides host memcpy on nproc core(s).
    ici_bw = 45e9
    wire_s = 2 * (8 - 1) / 8 * param_bytes / ici_bw
    shard_compute_ms = rows[0]["dp_step_ms"]  # batch 256 on one device
    out = {
        "protocol": "sync DP (ONE fused in-graph gradient AllReduce/step), "
                    "MLP 784-256-128-10 fp32, virtual CPU mesh, weak scaling "
                    "at 256 samples/device. dp_overhead_efficiency = "
                    "same-global-batch single-device step / sharded step "
                    "(identical FLOPs on identical silicon; ideal 1.0). "
                    "ablated_step_ms re-runs the identical sharded program "
                    "with psum ablated, so collective_ms = dp − ablated and "
                    "mesh_overhead_ms = ablated − single isolate the "
                    "AllReduce from the virtual-mesh artifact. Ref posture: "
                    "Spark local[8], SparkDl4jMultiLayer.java:183-203",
        "host": {"nproc": nproc, "platform": "cpu (virtual devices)"},
        "grad_allreduce_payload_bytes": param_bytes,
        "scaling": rows,
        "analysis": {
            "binding_constraint": (
                f"This host exposes nproc={nproc} core(s); all {rows[-1]['devices']} "
                "virtual devices time-share it. mesh_overhead_ms (ablated − "
                "single) is therefore serialization of n per-shard programs "
                "on shared core(s) + the loss of single-kernel batching — an "
                "artifact with no analogue on a real pod, where each chip "
                "owns its MXU. The framework-attributable cost is "
                "collective_ms only: the single fused AllReduce the step "
                "issues (all_reduce_ops_per_step confirms the count from "
                "compiled HLO)."),
            "two_device_real_vs_ideal": (
                f"n=2: dp={rows[1]['dp_step_ms']}ms vs ideal(single, same "
                f"batch)={rows[1]['single_device_same_batch_ms']}ms; the gap "
                f"splits into mesh_overhead={rows[1]['mesh_overhead_ms']}ms "
                f"(virtual-mesh serialization, vanishes on 2 real chips) + "
                f"collective={rows[1]['collective_ms']}ms (the AllReduce)."),
            "ici_projection": {
                "payload_mb": round(param_bytes / 1e6, 3),
                "ring_allreduce_wire_us_at_8x45GBps": round(wire_s * 1e6, 1),
                "per_shard_compute_ms_b256": shard_compute_ms,
                "projected_efficiency_8_chips": round(
                    shard_compute_ms
                    / (shard_compute_ms + wire_s * 1e3), 4),
                "note": "on real v5e ICI the fused grad AllReduce wire time "
                        "is ~2 orders below per-shard compute; the measured "
                        "collective_ms here is host-memcpy-bound and is an "
                        "upper bound on the framework's collective cost",
            },
            "collective_only_efficiency_8": r8["collective_only_efficiency"],
        },
    }
    with open(OUT, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
