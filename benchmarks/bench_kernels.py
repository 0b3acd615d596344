"""Kernel bench (paper §5.1 custom-kernel analogue): correctness vs oracle +
modeled TPU-v5e roofline time per kernel call, plus XLA-path wall time on
this host for reference. Pallas interpret-mode wall time is NOT a TPU number
and is reported only as `interp_ms` for completeness."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row
from repro.kernels.maxsim.maxsim import maxsim_pallas
from repro.kernels.maxsim.ref import maxsim_ref
from repro.kernels.ivf_scan.ivf_scan import ivf_scan_pallas
from repro.kernels.ivf_scan.ref import ivf_scan_ref
from repro.roofline.analysis import device_peaks

# modelled times use the published v5e peaks, not a measurement
_PEAKS = device_peaks("TPU v5 lite")
PEAK_FLOPS, HBM_BW = _PEAKS["bf16_flops"], _PEAKS["hbm_bw"]


def _wall(f, *args, n=5):
    f(*args)[0].block_until_ready() if isinstance(f(*args), tuple) else \
        jax.block_until_ready(f(*args))
    t0 = time.time()
    for _ in range(n):
        jax.block_until_ready(f(*args))
    return (time.time() - t0) / n


def main() -> list[str]:
    out = []
    rng = np.random.default_rng(0)
    ref_jit = jax.jit(maxsim_ref)

    for (lq, k, t, d) in ((32, 1000, 180, 32), (32, 128, 180, 32),
                          (16, 1000, 64, 128)):
        q = jnp.asarray(rng.standard_normal((lq, d)), jnp.float32)
        qm = jnp.ones(lq)
        docs = jnp.asarray(rng.standard_normal((k, t, d)), jnp.float32)
        lens = jnp.asarray(rng.integers(8, t + 1, k), jnp.int32)
        err = float(np.abs(np.asarray(
            maxsim_pallas(q, qm, docs, lens) - maxsim_ref(q, qm, docs, lens))).max())
        flops = 2.0 * k * lq * t * d
        byts = (k * t * d + lq * d) * 4 + k * 4
        model_us = max(flops / PEAK_FLOPS, byts / HBM_BW) * 1e6
        xla_us = _wall(ref_jit, q, qm, docs, lens) * 1e6
        out.append(row(
            f"kernel/maxsim/k={k},t={t},d={d}", xla_us,
            f"err={err:.1e} tpu_model_us={model_us:.1f} "
            f"arith_intensity={flops/byts:.1f}"))

    from repro.kernels.flash_decode.ref import flash_decode_ref
    from repro.kernels.flash_decode.flash_decode import flash_decode_pallas
    fd_ref = jax.jit(flash_decode_ref)
    for (b, s_, kv, g, dh) in ((8, 32768, 8, 8, 128), (4, 4096, 2, 7, 64)):
        q = jnp.asarray(rng.standard_normal((b, kv, g, dh)), jnp.bfloat16)
        kc = jnp.asarray(rng.standard_normal((b, min(s_, 2048), kv, dh)),
                         jnp.bfloat16)
        vc = kc
        lens = jnp.full((b,), kc.shape[1], jnp.int32)
        err = float(np.abs(
            np.asarray(flash_decode_pallas(q, kc, vc, lens, chunk=512),
                       np.float32)
            - np.asarray(fd_ref(q, kc, vc, lens), np.float32)).max())
        flops = 4.0 * b * kv * g * s_ * dh
        byts = 2.0 * b * s_ * kv * dh * 2
        model_us = max(flops / PEAK_FLOPS, byts / HBM_BW) * 1e6
        out.append(row(f"kernel/flash_decode/b={b},s={s_}", 0.0,
                       f"err={err:.1e} tpu_model_us={model_us:.1f} "
                       f"(memory-bound: AI={flops/byts:.1f})"))

    ref2 = jax.jit(ivf_scan_ref)
    for (b, n, d) in ((32, 32768, 128), (8, 65536, 128)):
        q = jnp.asarray(rng.standard_normal((b, d)), jnp.float32)
        c = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
        flops = 2.0 * b * n * d
        byts = (n * d + b * d + b * n) * 4
        model_us = max(flops / PEAK_FLOPS, byts / HBM_BW) * 1e6
        xla_us = _wall(ref2, q, c) * 1e6
        sub = ivf_scan_pallas(q[:, :64], c[:512, :64])
        err = float(np.abs(np.asarray(sub - ivf_scan_ref(q[:, :64],
                                                         c[:512, :64]))).max())
        out.append(row(f"kernel/ivf_scan/b={b},n={n}", xla_us,
                       f"err={err:.1e} tpu_model_us={model_us:.1f}"))
    return out


if __name__ == "__main__":
    main()
