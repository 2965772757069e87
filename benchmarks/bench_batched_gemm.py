"""Paper Table 2: batched small-GEMM peak throughput vs block size.

The paper measures cuBLAS batched gemm on K20 GPUs.  Our target is the
TPU MXU; on this CPU-only box we report (a) measured XLA-fallback
throughput (relative trend) and (b) the roofline-PROJECTED TPU v5e
throughput per block size: util = min(1, AI / (peak/bw)) where
AI = bs/3 flops/byte (bf16) for a streamed batch, against the v5e ridge
of 197e12/819e9 = 241 flops/byte.  This reproduces the paper's
observation that small blocks starve the compute unit — on the MXU the
starvation is worse, which is why the leaf block is retuned to 128+
(DESIGN.md §3).  CSV: bs,batch,cpu_gflops,ai_flops_per_byte,
projected_v5e_gflops,pct_peak.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref

PEAK = 197e12
BW = 819e9


def main() -> None:
    print("bs,batch,cpu_gflops,ai_flops_per_byte,projected_v5e_gflops,"
          "pct_peak")
    rng = np.random.default_rng(0)
    for bs in (16, 32, 48, 64, 96, 128):
        batch = max(1, (1 << 22) // (bs * bs))     # ~4M elements per op
        a = jnp.asarray(rng.standard_normal((batch, bs, bs)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((batch, bs, bs)), jnp.float32)
        f = jax.jit(ref.batched_gemm_ref)
        f(a, b).block_until_ready()
        t0 = time.perf_counter()
        reps = 3
        for _ in range(reps):
            f(a, b).block_until_ready()
        dt = (time.perf_counter() - t0) / reps
        flops = 2.0 * batch * bs ** 3
        cpu_gflops = flops / dt / 1e9
        # streamed batch (unique A, B, C per multiply), bf16:
        # bytes = 3 * bs^2 * 2 per op -> AI = 2 bs^3 / 6 bs^2 = bs / 3
        ai = bs / 3.0
        ridge = PEAK / BW
        proj = PEAK * min(1.0, ai / ridge)
        print(f"{bs},{batch},{cpu_gflops:.1f},{ai:.1f},"
              f"{proj/1e9:.0f},{100 * proj / PEAK:.1f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
