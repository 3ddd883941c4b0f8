"""Per-arch smoke tests: REDUCED same-family config, one forward + one train
step on CPU, asserting output shapes and finiteness (assignment requirement).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.launch.steps import make_train_step
from repro.models import model_defs, forward_train
from repro.models.params import init_params
from repro.optim.adamw import AdamWConfig, init_opt_state


def _batch(cfg, b=2, s=32):
    rng = np.random.default_rng(0)
    out = {"labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)),
                                 jnp.int32)}
    if cfg.modality == "text":
        out["tokens"] = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)),
                                    jnp.int32)
    else:
        out["embeds"] = jnp.asarray(
            rng.normal(size=(b, s, cfg.d_model)).astype(np.float32) * 0.02,
        ).astype(jnp.bfloat16)
    return out


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward(arch):
    cfg = get_smoke_config(arch)
    params = init_params(model_defs(cfg), seed=0)
    batch = _batch(cfg)
    lg, aux = jax.jit(lambda p, b: forward_train(p, b, cfg))(params, batch)
    assert lg.shape == (2, 32, cfg.padded_vocab)
    assert bool(jnp.isfinite(lg).all()), arch
    assert bool(jnp.isfinite(aux)), arch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step(arch):
    cfg = get_smoke_config(arch)
    params = init_params(model_defs(cfg), seed=0)
    opt = init_opt_state(params)
    step = jax.jit(make_train_step(cfg, AdamWConfig(lr=1e-3, total_steps=10)))
    p2, o2, metrics = step(params, opt, _batch(cfg))
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    # params actually moved
    delta = sum(float(jnp.max(jnp.abs(a - b)))
                for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)))
    assert delta > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_matches_assignment(arch):
    """The FULL configs carry the exact published shapes (never materialized
    here — exercised via the dry-run's ShapeDtypeStructs)."""
    cfg = get_config(arch)
    expect = {
        "jamba_1_5_large_398b": (72, 8192, 64, 8, 24576, 65536),
        "qwen2_7b": (28, 3584, 28, 4, 18944, 152064),
        "olmo_1b": (16, 2048, 16, 16, 8192, 50304),
        "stablelm_12b": (40, 5120, 32, 8, 13824, 100352),
        "deepseek_67b": (95, 8192, 64, 8, 22016, 102400),
        "musicgen_medium": (48, 1536, 24, 24, 6144, 2048),
        "internvl2_26b": (48, 6144, 48, 8, 16384, 92553),
        "qwen3_moe_235b_a22b": (94, 4096, 64, 4, 1536, 151936),
        "moonshot_v1_16b_a3b": (48, 2048, 16, 16, 1408, 163840),
        "mamba2_780m": (48, 1536, 1, 1, 0, 50280),
    }[arch]
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
           cfg.d_ff, cfg.vocab_size)
    assert got == expect
    if arch == "jamba_1_5_large_398b":
        assert cfg.attn_every == 8 and cfg.n_experts == 16 and cfg.top_k == 2
    if arch == "qwen3_moe_235b_a22b":
        assert cfg.n_experts == 128 and cfg.top_k == 8
    if arch == "moonshot_v1_16b_a3b":
        assert cfg.n_experts == 64 and cfg.top_k == 6
    if arch == "mamba2_780m":
        assert cfg.ssm_state == 128 and cfg.family == "ssm"
    if arch == "qwen2_7b":
        assert cfg.qkv_bias
    if arch == "olmo_1b":
        assert cfg.norm == "nonparam"


def test_param_counts_match_published():
    """Analytic param counts land on the published model sizes."""
    cases = {"jamba_1_5_large_398b": (398e9, 0.02),
             "qwen2_7b": (7.6e9, 0.03),
             "deepseek_67b": (67e9, 0.03),
             "qwen3_moe_235b_a22b": (235e9, 0.02),
             "mamba2_780m": (0.78e9, 0.05)}
    for arch, (want, tol) in cases.items():
        got = get_config(arch).param_count()
        assert abs(got - want) / want < tol, (arch, got)
    active = get_config("qwen3_moe_235b_a22b").active_param_count()
    assert abs(active - 22e9) / 22e9 < 0.05


def test_init_params_identical_across_processes():
    """Leaf keys come from a stable digest of the path, so two interpreters
    (each with its own ``str`` hash salt) draw the same weights."""
    import os
    import subprocess
    import sys
    code = (
        "import jax, numpy as np\n"
        "from repro.configs import get_smoke_config\n"
        "from repro.models import model_defs\n"
        "from repro.models.params import init_params\n"
        "p = init_params(model_defs(get_smoke_config('olmo_1b')), seed=3)\n"
        "print([float(np.abs(np.asarray(x, np.float64)).sum())\n"
        "       for x in jax.tree.leaves(p)])\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(root, "src"))
    sums = []
    for salt in ("1", "2"):
        env["PYTHONHASHSEED"] = salt
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=300,
                             check=True)
        sums.append(out.stdout.strip().splitlines()[-1])
    assert sums[0] == sums[1]
