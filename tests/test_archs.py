"""Per-architecture smoke tests: reduced same-family config, one forward +
one train step on CPU, asserting output shapes and finiteness (assignment
requirement f)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SHAPES
from repro.configs.registry import ARCHS, get_config, get_module
from repro.models import decoder, encdec
from repro.nn.param import split_tree
from repro.optim.adamw import AdamWConfig
from repro.train.step import TrainConfig, init_train_state, make_train_step

LM_ARCHS = [a for a in ARCHS if a != "ising-qmc"]
B, S = 2, 32


def _batch(cfg, rng):
    batch = {}
    text_len = S
    if cfg.vlm_patches:
        text_len = S - cfg.vlm_patches
        batch["visual_embeds"] = jnp.asarray(
            rng.standard_normal((B, cfg.vlm_patches, cfg.d_model), np.float32)
        )
    if cfg.encdec:
        batch["frames"] = jnp.asarray(
            rng.standard_normal((B, cfg.enc_seq, cfg.d_model), np.float32)
        )
    batch["tokens"] = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (B, text_len)), jnp.int32
    )
    batch["labels"] = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (B, text_len)), jnp.int32
    )
    return batch


@pytest.mark.parametrize("arch", LM_ARCHS)
def test_arch_smoke_forward_and_train_step(arch):
    cfg = get_config(arch, smoke=True)
    rng = np.random.default_rng(0)
    init_fn = encdec.init_params if cfg.encdec else decoder.init_params
    params, _ = split_tree(init_fn(jax.random.PRNGKey(0), cfg))
    batch = _batch(cfg, rng)

    # forward
    if cfg.encdec:
        logits, aux = encdec.apply(params, batch["tokens"], batch["frames"], cfg)
    else:
        logits, aux = decoder.apply(
            params, batch["tokens"], cfg, visual_embeds=batch.get("visual_embeds")
        )
    assert logits.shape[0] == B and logits.shape[-1] == cfg.padded_vocab
    assert logits.shape[1] == S if not cfg.encdec else batch["tokens"].shape[1]
    assert np.isfinite(np.asarray(logits, np.float32)).all(), arch
    assert np.isfinite(float(aux))

    # one train step
    tc = TrainConfig(optimizer=AdamWConfig(warmup_steps=1, total_steps=10))
    state = init_train_state(params, tc)
    step = jax.jit(make_train_step(cfg, tc), donate_argnums=(0,))
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"])), arch
    assert int(state.step) == 1


@pytest.mark.parametrize(
    "arch",
    ["qwen2.5-14b", "deepseek-v3-671b", "zamba2-1.2b", "rwkv6-1.6b"],
)
def test_arch_decode_matches_teacher_forcing(arch):
    """KV-cache / SSM-state / MLA-absorbed decode must reproduce the
    teacher-forced logits step by step."""
    cfg = get_config(arch, smoke=True)
    rng = np.random.default_rng(1)
    params, _ = split_tree(decoder.init_params(jax.random.PRNGKey(0), cfg))
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 16)), jnp.int32)
    lg_tf, _ = decoder.apply(params, toks, cfg)
    caches = decoder.init_decode_caches(cfg, B, 16)
    for t in range(4):
        lg, caches = decoder.decode_step(params, toks[:, t : t + 1], caches, jnp.int32(t), cfg)
        tf = np.asarray(lg_tf[:, t], np.float32)
        dc = np.asarray(lg[:, 0], np.float32)
        err = np.abs(tf - dc).max() / (np.abs(tf).max() + 1e-6)
        assert err < 0.06, (arch, t, err)


def test_full_configs_match_assignment():
    """Exact published numbers from the assignment table."""
    expect = {
        "qwen2.5-14b": (48, 5120, 40, 8, 13824, 152064),
        "deepseek-coder-33b": (62, 7168, 56, 8, 19200, 32256),
        "gemma-2b": (18, 2048, 8, 1, 16384, 256000),
        "command-r-35b": (40, 8192, 64, 8, 22528, 256000),
        "zamba2-1.2b": (38, 2048, 32, 32, 8192, 32000),
        "rwkv6-1.6b": (24, 2048, 32, 32, 7168, 65536),
        "deepseek-v3-671b": (61, 7168, 128, 128, 18432, 129280),
        "llama4-scout-17b-a16e": (48, 5120, 40, 8, 8192, 202048),
        "internvl2-26b": (48, 6144, 48, 8, 16384, 92553),
        "whisper-tiny": (4, 384, 6, 6, 1536, 51865),
    }
    for arch, (L, d, h, kv, ff, v) in expect.items():
        cfg = get_config(arch)
        assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                cfg.d_ff, cfg.vocab_size) == (L, d, h, kv, ff, v), arch
    # family-specific invariants
    dv3 = get_config("deepseek-v3-671b")
    assert dv3.moe.num_experts == 256 and dv3.moe.top_k == 8
    assert dv3.moe.d_ff_expert == 2048 and dv3.mla.kv_lora_rank == 512
    l4 = get_config("llama4-scout-17b-a16e")
    assert l4.moe.num_experts == 16 and l4.moe.top_k == 1
    assert get_config("zamba2-1.2b").mamba.d_state == 64
    assert get_config("gemma-2b").head_dim == 256


def test_input_specs_cells():
    """Every (arch x shape) produces specs or a documented skip."""
    from repro.configs.base import SkipCell

    runs, skips = 0, 0
    for arch in LM_ARCHS:
        mod = get_module(arch)
        for shape in SHAPES.values():
            try:
                kind, inputs = mod.input_specs(shape)
                leaves = jax.tree_util.tree_leaves(inputs)
                assert leaves and all(hasattr(l, "shape") for l in leaves)
                runs += 1
            except SkipCell:
                assert shape.name == "long_500k"
                skips += 1
    assert runs == 32 and skips == 8  # 40 assigned cells total


def test_moe_param_counts_sane():
    dv3 = get_config("deepseek-v3-671b")
    n = dv3.num_params()
    assert 6.3e11 < n < 7.2e11, n  # ~671B
    na = dv3.num_active_params()
    assert 3.0e10 < na < 4.5e10, na  # ~37B active
