"""Pallas kernel micro-benches.

On this CPU container kernels execute in interpret mode (correctness, not
speed), so wall-times here time the *reference* jnp path (the XLA fallback a
TPU would beat) and validate kernel-vs-ref agreement at bench shapes; the
kernels' TPU roofline expectations are derived analytically from their
BlockSpec tiling and reported as `derived`."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref
from repro.utils.device_peaks import TARGET_KIND, peaks

# the roofline columns model one TPU v5e chip, whatever this host runs on
PEAK_FLOPS = peaks(TARGET_KIND)["flops_bf16"]
HBM_BW = peaks(TARGET_KIND)["hbm_bytes_per_s"]


def _time(fn, *args, reps=3):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.time()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / reps * 1e6


def main(fast: bool = False) -> list[str]:
    out = ["name,us_per_call,derived"]
    rng = np.random.default_rng(0)

    # --- ucb_scores: memory-bound, 1 HBM pass over 3 arrays
    k = 2 ** 17 if fast else 2 ** 20
    sums = jnp.asarray(rng.uniform(0, 1e3, k), jnp.float32)
    n_sel = jnp.asarray(rng.integers(0, 50, k), jnp.int32)
    total = jnp.asarray(int(n_sel.sum()))
    us = _time(lambda: jax.jit(ref.ucb_scores_ref)(sums, n_sel, total))
    got = ops.ucb_scores(sums, n_sel, total, interpret=True)
    err = float(jnp.max(jnp.abs(got - ref.ucb_scores_ref(sums, n_sel, total))))
    tpu_us = (k * 12) / HBM_BW * 1e6
    out.append(f"kernels/ucb_scores_k{k},{us:.1f},"
               f"maxerr={err:.2e} tpu_roofline_us={tpu_us:.1f}")

    # --- fedavg: streaming weighted sum, (C+1)/C of input bytes
    c, n = 5, (1 << 20 if fast else 1 << 23)
    stacked = jnp.asarray(rng.standard_normal((c, n)), jnp.float32)
    w = jnp.asarray(rng.dirichlet(np.ones(c)), jnp.float32)
    us = _time(lambda: jax.jit(ref.fedavg_ref)(stacked, w))
    got = ops.fedavg_combine(stacked, w, interpret=True)
    err = float(jnp.max(jnp.abs(got - ref.fedavg_ref(stacked, w))))
    tpu_us = (c + 1) * n * 4 / HBM_BW * 1e6
    out.append(f"kernels/fedavg_c{c}_n{n},{us:.1f},"
               f"maxerr={err:.2e} tpu_roofline_us={tpu_us:.1f}")

    # --- flash attention fwd: compute-bound
    b, s, kv, g, dh = 1, (512 if fast else 2048), 2, 2, 128
    q = jnp.asarray(rng.standard_normal((b, s, kv, g, dh)), jnp.bfloat16)
    kk = jnp.asarray(rng.standard_normal((b, s, kv, dh)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((b, s, kv, dh)), jnp.bfloat16)
    us = _time(lambda: jax.jit(ref.flash_attention_ref)(q, kk, v))
    got = ops.flash_attention(q, kk, v, interpret=True, block_q=256,
                              block_kv=256)
    want = ref.flash_attention_ref(q, kk, v)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) -
                                want.astype(jnp.float32))))
    flops = 4 * b * kv * g * s * s * dh
    tpu_us = flops / PEAK_FLOPS * 1e6
    out.append(f"kernels/flash_s{s},{us:.1f},"
               f"maxerr={err:.2e} tpu_roofline_us={tpu_us:.2f}")

    # --- rg_lru: memory-bound scan (1 read of a,b + 1 write of y)
    b2, t, w2 = 2, (512 if fast else 2048), 1024
    a = jnp.asarray(rng.uniform(0.8, 0.999, (b2, t, w2)), jnp.float32)
    bb = jnp.asarray(rng.standard_normal((b2, t, w2)) * 0.1, jnp.float32)
    us = _time(lambda: jax.jit(ref.rg_lru_ref)(a, bb))
    got = ops.rg_lru_scan(a, bb, interpret=True)
    err = float(jnp.max(jnp.abs(got - ref.rg_lru_ref(a, bb))))
    tpu_us = 3 * b2 * t * w2 * 4 / HBM_BW * 1e6
    out.append(f"kernels/rg_lru_t{t},{us:.1f},"
               f"maxerr={err:.2e} tpu_roofline_us={tpu_us:.1f}")
    return out


if __name__ == "__main__":
    for line in main():
        print(line)
