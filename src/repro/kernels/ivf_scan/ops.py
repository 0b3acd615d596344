"""Jit'd centroid-scoring op with Pallas/XLA dispatch."""
from __future__ import annotations

import jax

from repro.kernels.ivf_scan.ivf_scan import ivf_scan_pallas
from repro.kernels.ivf_scan.ref import ivf_scan_ref


@jax.jit
def _ref_jit(q, centroids):
    return ivf_scan_ref(q, centroids)


def centroid_scores(q, centroids, *, use_pallas: bool = False,
                    interpret: bool | None = None, block_n: int = 128):
    if use_pallas:
        return ivf_scan_pallas(q, centroids, block_n=block_n,
                               interpret=interpret)
    return _ref_jit(q, centroids)
