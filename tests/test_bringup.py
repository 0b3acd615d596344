"""What decides where the program runs: Pallas interpret mode from the
backend, the per-device peak table, the compile-cache location, and the chip
smoke script's refusal to run without a TPU (plus its phases at a tiny size
on the CPU)."""
import json
import os
import sys

import jax
import pytest

from repro.compile_cache import REPO_CACHE_DIR, enable_compile_cache
from repro.kernels import resolve_interpret
from repro.roofline.analysis import DEVICE_PEAKS, device_peaks

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("platform,expected", [("cpu", True),
                                               ("tpu", False)])
def test_interpret_follows_backend(monkeypatch, platform, expected):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    assert resolve_interpret() is expected
    assert resolve_interpret(not expected) is (not expected)


@pytest.mark.parametrize("platform", ["gpu", "METAL"])
def test_interpret_unknown_platform_raises(monkeypatch, platform):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    with pytest.raises(RuntimeError, match=platform):
        resolve_interpret()


def test_kernel_op_interprets_on_cpu():
    """The served call sites pass no ``interpret``: on the CPU the kernel
    must run interpreted (and agree with its oracle), never be compiled."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.maxsim.ops import maxsim
    from repro.kernels.maxsim.ref import maxsim_ref
    r = np.random.default_rng(0)
    q = jnp.asarray(r.standard_normal((5, 32)), jnp.float32)
    docs = jnp.asarray(r.standard_normal((20, 11, 32)), jnp.float32)
    lens = jnp.asarray(r.integers(1, 12, 20), jnp.int32)
    out = maxsim(q, jnp.ones(5), docs, lens, use_pallas=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(maxsim_ref(q, jnp.ones(5), docs,
                                                     lens)),
                               rtol=1e-5, atol=1e-5)


def test_peak_table_v5e():
    p = device_peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bw"] == 819e9
    assert p["ici_links"] * p["ici_link_bw"] * 8 == 1600e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5e", ""])
def test_peak_table_unknown_kind_raises(kind):
    assert kind not in DEVICE_PEAKS
    with pytest.raises(KeyError, match="no published peaks"):
        device_peaks(kind)


@pytest.fixture
def cache_dir_restored():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_honours_env(monkeypatch, tmp_path,
                                   cache_dir_restored):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_repo_dir(monkeypatch, cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = enable_compile_cache()
    assert path == str(REPO_CACHE_DIR) == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert enable_compile_cache() == path          # stable across calls


@pytest.fixture(scope="module")
def chip_smoke():
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    return chip_smoke


def test_chip_smoke_refuses_cpu(chip_smoke, capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main([]) == 2
    out, err = capsys.readouterr()
    assert '"ok"' not in out
    assert "needs a TPU" in err


TINY = dict(docs=1500, queries=6, k=60, nprobe=8, encode_batch=2,
            fde_docs=700, fde_batch=3, timeout_s=300.0)


def test_chip_smoke_phases_tiny_cpu(chip_smoke, capsys):
    """Every phase of the smoke (build, four served windows checked against
    the host oracles, Pallas vs XLA, the bitsim/fdescan kernel checks,
    encoder) at a tiny size."""
    from repro.configs.base import get_config
    from repro.models.colberter import smoke_config
    failures = chip_smoke.run(chip_smoke.Sizes(**TINY), "cpu",
                              encoder_cfg=smoke_config(get_config(
                                  "colberter")))
    assert failures == []
    out = capsys.readouterr().out
    for phase in ("xla/cold", "xla/warm", "pallas/cold", "pallas/warm"):
        assert f"serve {phase}: 6 queries, errors 0 shed 0 timeouts 0" in out
    assert out.count("candidates failing the IVF oracle 0,") == 4
    assert "pallas vs xla: same candidate sets: True" in out
    assert "bitsim K 60 x T 180 x D 32" in out
    assert "modelled (ComputeModel + SSD clock, not measured)" in out
    assert not any(line.startswith("{") and json.loads(line).get("ok")
                   for line in out.splitlines())


def test_host_ivf_oracle_refuses_wrong_candidates(chip_smoke):
    """The candidate check admits the plain float32 top-k and refuses a
    set with a certain top-k document swapped for one of an unprobed cell,
    a duplicate, or a short list."""
    import numpy as np

    from repro.core.ivf import build_ivf
    r = np.random.default_rng(0)
    cls = r.standard_normal((3000, 16)).astype(np.float32)
    cls /= np.linalg.norm(cls, axis=1, keepdims=True)
    idx = build_ivf(cls, 30, seed=0)
    ivf = chip_smoke.HostIVF(idx, cls, nprobe=4, k=50)
    q = cls[7] + 0.1 * r.standard_normal(16).astype(np.float32)
    q /= np.linalg.norm(q)
    exp = ivf.expect(q)
    assert ivf.check(exp, exp["ref"]) == []
    assert ivf.check(exp, exp["ref"][::-1]) == []
    far = np.setdiff1d(np.arange(3000), exp["may"])[:1]
    swapped = np.concatenate([exp["ref"][1:], far])
    assert len(ivf.check(exp, swapped)) == 2        # one missing, one extra
    assert ivf.check(exp, np.concatenate([exp["ref"][:-1],
                                          exp["ref"][:1]]))
    assert ivf.check(exp, exp["ref"][:-5])
