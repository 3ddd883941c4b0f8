"""``chip_smoke.py`` off the chip: it must refuse, never fall back."""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def test_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    for line in out.stdout.splitlines():
        try:
            assert json.loads(line).get("ok") is not True
        except (json.JSONDecodeError, AttributeError):
            pass


def test_compile_cache_placement():
    """An outside JAX_COMPILATION_CACHE_DIR wins (the script sets nothing);
    otherwise the cache has one fixed home inside the checkout."""
    assert chip_smoke.compile_cache_dir(
        {"JAX_COMPILATION_CACHE_DIR": "/elsewhere"}, "/co") is None
    assert chip_smoke.compile_cache_dir({}, "/co") == "/co/.jax_cache"
    assert chip_smoke.compile_cache_dir({}) == os.path.join(ROOT,
                                                            ".jax_cache")


def test_one_chip_phase_at_smoke_size(monkeypatch):
    """The one-chip phase end to end on a reduced OLMo, with selection
    steered as on a TPU and the Pallas kernels interpreted: the plan is
    all ``pallas:*``, attention is fused, and it agrees with the xla path."""
    import jax

    from repro.configs import get_smoke_config
    monkeypatch.setenv("STRUM_INTERPRET", "1")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(chip_smoke, "MAX_LEN", 128)
    got = chip_smoke.one_chip(chip_smoke.CompileClock(),
                              get_smoke_config("olmo_1b"),
                              lens=(16, 24, 33, 40))
    assert got["agreement"] >= chip_smoke.AGREE_MIN
    assert got["prefill_rel_l2"] <= chip_smoke.LOGIT_RTOL
