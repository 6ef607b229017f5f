"""``chip_smoke.py``'s phases (b)-(d) on the CPU: the same functions the
chip run calls, at ``.reduced()`` size with the Pallas kernels in
interpret mode (the script itself has no CPU path — it stops in phase
(a) without a TPU). Also the compile-cache location rule."""
import importlib.util
import os
import pathlib

import pytest

from repro.compat import cache
from repro.configs import get_config
from repro.kernels import ops as kops

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def interpret_backend():
    mode = kops.BACKEND.mode
    kops.set_backend("pallas_interpret")
    yield
    kops.set_backend(mode)


def test_phase_kernels_reduced(smoke):
    res = smoke.phase_kernels(get_config("qwen3_8b").reduced(),
                              interpret=True, slots=2, seq=64, page=8)
    assert len(res) == 15
    assert all(rel <= smoke.KERNEL_TOL for _, rel in res.values())


def test_phase_serve_and_backends_reduced(smoke, interpret_backend):
    cfg = get_config("qwen3_8b").reduced()
    eng, report = smoke.phase_serve(cfg, n_requests=3, prompt_range=(9, 30),
                                    new_tokens=3, max_seq_len=48, page=8,
                                    slots=2)
    assert report["tokens"] == 9
    assert "paged_attention/fused_paged" in report["dispatch_paths"]
    out = smoke.phase_backends(eng, n_prompts=2, prompt_range=(4, 12))
    assert out["argmax_kernel"] == out["argmax_oracle"]


def test_phase_device_refuses_cpu(smoke):
    with pytest.raises(smoke.PhaseError, match="no TPU"):
        smoke.phase_device("unused")


def test_compile_cache_dir_rule(monkeypatch):
    monkeypatch.delenv(cache.ENV_VAR, raising=False)
    assert cache.compile_cache_dir() == str(ROOT / ".jax_cache")
    assert cache.compile_cache_dir({cache.ENV_VAR: "/x/y"}) == "/x/y"
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
    assert os.path.isabs(cache.compile_cache_dir())
