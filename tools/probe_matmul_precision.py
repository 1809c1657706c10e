import time, statistics
import jax, jax.numpy as jnp

N = 4096

def bench(dtype, precision, steps):
    a = jax.random.normal(jax.random.PRNGKey(0), (N, N)).astype(dtype)
    b = jax.random.normal(jax.random.PRNGKey(1), (N, N)).astype(dtype)

    @jax.jit
    def prog(a, b):
        def body(carry, _):
            c = jnp.dot(carry, b, precision=precision)
            c = c / jnp.float32(64.0).astype(c.dtype)
            return c, ()
        out, _ = jax.lax.scan(body, a, None, length=steps)
        return jnp.sum(out.astype(jnp.float32))

    float(prog(a, b))  # compile+warm
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        float(prog(a, b))
        times.append(time.perf_counter() - t0)
    t = statistics.median(times)
    flops = 2 * N**3 * steps
    return flops / t / 1e12, t

for dtype, prec, label, steps in [
    (jnp.bfloat16, jax.lax.Precision.DEFAULT, "bf16_default", 4096),
    (jnp.float32, jax.lax.Precision.DEFAULT, "fp32_default", 4096),
    (jnp.float32, jax.lax.Precision.HIGH, "fp32_high", 2048),
    (jnp.float32, jax.lax.Precision.HIGHEST, "fp32_highest", 512),
]:
    tf, t = bench(dtype, prec, steps)
    print(f"{label}: {tf:.1f} TFLOP/s (run {t:.2f}s)")

# Measured 2026-07-30 on the driver's TPU v5 lite chip (rounds 4-5 record):
#   bf16_default: 185.7 TFLOP/s   (94% of the 197 TF/s spec peak)
#   fp32_default: 153.5 TFLOP/s   (same single-bf16-pass MXU path; the gap
#                                  is fp32 operand HBM traffic)
#   fp32_high:     59.5 TFLOP/s   (bf16x3 passes)
#   fp32_highest:  29.7 TFLOP/s   (bf16x6 passes ~ true fp32 accuracy)
