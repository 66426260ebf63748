"""Adaptive-controller tests + per-arch sharding-mode selection tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import AbstractMesh
from repro import configs as cfgs
from repro.core import adaptive, error as err
from repro.distributed import sharding as shd


# ---------------------------------------------------------------------------
# Adaptive budget controller (paper §4.2/§7)
# ---------------------------------------------------------------------------

def _stats(counts, s2):
    counts = jnp.asarray(counts, jnp.int32)
    y = jnp.minimum(counts, 64)
    mean = jnp.zeros_like(s2)
    yf = y.astype(jnp.float32)
    return err.StratumStats(counts=counts, taken=y,
                            sums=mean * yf,
                            sumsqs=jnp.asarray(s2) * (yf - 1) + 0.0)


def test_feedback_grows_sample_on_violation():
    budget = adaptive.accuracy_budget(0.5, 0.95, min_per_stratum=4,
                                      max_per_stratum=10_000)
    stats = _stats([10_000, 10_000], jnp.array([100.0, 100.0]))
    ok = err.Estimate(value=jnp.float32(1.0), variance=jnp.float32(0.001))
    bad = err.Estimate(value=jnp.float32(1.0), variance=jnp.float32(4.0))
    cap_ok = adaptive.next_capacity(budget, stats, ok)
    cap_bad = adaptive.next_capacity(budget, stats, bad)
    assert int(jnp.sum(cap_bad)) > int(jnp.sum(cap_ok))


def test_capacity_clamped():
    budget = adaptive.accuracy_budget(1e-6, 0.95, min_per_stratum=4,
                                      max_per_stratum=128)
    stats = _stats([100_000], jnp.array([1e6]))
    cap = adaptive.next_capacity(budget, stats)
    assert int(cap[0]) == 128


def test_throughput_budget():
    cap = adaptive.throughput_budget_capacity(65_536, 0.5, 4)
    np.testing.assert_array_equal(np.asarray(cap), [8192] * 4)


# ---------------------------------------------------------------------------
# Attention/MoE TP mode selection (DESIGN.md §6)
# ---------------------------------------------------------------------------

MESH = AbstractMesh((16, 16), ("data", "model"))

EXPECTED_MODE = {
    # kv divisible → kv_heads; else G divisible → q_group; else seq
    "seamless-m4t-large-v2": "kv_heads",    # kv=16
    "llama3-405b": "q_group",               # kv=8, G=16
    "recurrentgemma-9b": "q_group",         # kv=1, G=16
    "granite-34b": "q_group",               # kv=1, G=48
    "phi4-mini-3.8b": "attn_seq",           # kv=8, G=3
    "granite-moe-3b-a800m": "attn_seq",     # kv=8, G=3
    "kimi-k2-1t-a32b": "attn_seq",          # kv=8, G=8 → 8∤16 → seq
    "nemotron-4-15b": "attn_seq",           # G=6
    "internvl2-76b": "attn_seq",            # G=8
}


@pytest.mark.parametrize("arch,mode", sorted(EXPECTED_MODE.items()))
def test_attention_mode_selection(arch, mode):
    cfg = cfgs.get_config(arch)
    rules = shd.build_rules(cfg, MESH)
    active = [m for m in ("kv_heads", "q_group", "attn_seq")
              if rules[m] == "model"]
    assert active == [mode], f"{arch}: {active}"


def test_moe_expert_sharding_fallback():
    gm = shd.build_rules(cfgs.get_config("granite-moe-3b-a800m"), MESH)
    assert gm["experts"] is None and gm["expert_mlp"] == "model"  # 40 ∤ 16
    kimi = shd.build_rules(cfgs.get_config("kimi-k2-1t-a32b"), MESH)
    assert kimi["experts"] == "model" and kimi["expert_mlp"] is None


def test_resolve_spec_divisibility():
    cfg = cfgs.get_config("llama3-405b")
    rules = shd.build_rules(cfg, MESH)
    # kv_heads=8 not divisible → replicated even though rule asks model
    spec = shd.resolve_spec(("batch", None, "kv_heads", None),
                            (256, 4096, 8, 128), MESH, rules)
    assert spec[2] is None
    # batch folds pod+data when present
    mesh3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    spec3 = shd.resolve_spec(("batch", None), (256, 10), mesh3, rules)
    assert spec3[0] == ("pod", "data")


def test_sp_residual_rule():
    cfg = cfgs.get_config("phi4-mini-3.8b").replace(sp_residual=True)
    rules = shd.build_rules(cfg, MESH)
    assert rules["seq_res"] == "model"
    rules0 = shd.build_rules(cfgs.get_config("phi4-mini-3.8b"), MESH)
    assert rules0["seq_res"] is None
