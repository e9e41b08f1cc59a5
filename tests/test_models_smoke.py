"""Per-architecture smoke tests: REDUCED config of each family, one
forward + one train step on CPU, asserting shapes and no NaNs.

(The FULL configs are exercised only by the dry-run — see launch/dryrun.py.)
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ALL_ARCHS, get_config
from repro.models import (decode_step, forward, init_decode_state,
                          init_model, loss_fn)
from repro.optim import AdamWConfig
from repro.runtime import init_train_state, make_train_step


def small_cfg(name: str, **kw):
    cfg = get_config(name)
    reps = dict(n_layers=4, d_model=128, vocab_size=512,
                vocab_pad_multiple=128, dtype="float32",
                nystrom_landmarks=32, rls_keep_recent=8)
    if cfg.family in ("dense", "vlm", "audio", "moe"):
        reps.update(n_heads=4,
                    n_kv_heads=max(1, cfg.n_kv_heads * 4 // cfg.n_heads),
                    d_ff=256, head_dim=32)
    if cfg.family == "moe":
        reps["moe"] = dataclasses.replace(
            cfg.moe, n_experts=8, top_k=min(cfg.moe.top_k, 2),
            d_ff_expert=64, d_ff_shared=128,
            first_dense_ff=256 if cfg.moe.first_dense_ff else 0)
    if cfg.family in ("ssm", "hybrid"):
        reps["ssm"] = dataclasses.replace(cfg.ssm, d_state=16, head_dim=32,
                                          chunk=32)
    if cfg.family == "hybrid":
        reps["n_layers"] = 7
        reps["shared_attn_every"] = 3
    reps.update(kw)
    return dataclasses.replace(cfg, **reps)


def _batch(cfg, B=2, S=64, seed=0):
    key = jax.random.key(seed)
    if cfg.modality in ("vision", "audio"):
        emb = jax.random.normal(key, (B, S, cfg.d_model), jnp.float32)
        if cfg.modality == "audio":
            lab = jax.random.randint(key, (B, S, cfg.num_codebooks), 0,
                                     cfg.vocab_size)
        else:
            lab = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
        return {"embeds": emb, "labels": lab}
    toks = jax.random.randint(key, (B, S + 1), 0, cfg.vocab_size)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


@pytest.mark.parametrize("arch", ALL_ARCHS)
class TestArchSmoke:
    def test_forward_shapes_no_nan(self, arch):
        cfg = small_cfg(arch)
        params = init_model(cfg, jax.random.key(0))
        b = _batch(cfg)
        out = forward(params, cfg, tokens=b.get("tokens"),
                      embeds=b.get("embeds"))
        expect_v = cfg.padded_vocab
        if cfg.modality == "audio" and cfg.num_codebooks > 1:
            assert out.logits.shape == (2, 64, cfg.num_codebooks, expect_v)
        else:
            assert out.logits.shape == (2, 64, expect_v)
        assert not bool(jnp.isnan(out.logits).any())

    def test_train_step_decreases_nothing_nan(self, arch):
        cfg = small_cfg(arch)
        params = init_model(cfg, jax.random.key(0))
        opt_state, comp = init_train_state(cfg, params)
        step = jax.jit(make_train_step(
            cfg, AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10)))
        b = _batch(cfg)
        out = step(params, opt_state, comp, b)
        assert not bool(jnp.isnan(out.metrics["loss"]))
        assert float(out.metrics["grad_norm"]) > 0
        out2 = step(out.params, out.opt_state, out.comp_state, b)
        # same batch twice: loss must drop
        assert float(out2.metrics["loss"]) < float(out.metrics["loss"])

    def test_decode_step_advances(self, arch):
        cfg = small_cfg(arch)
        params = init_model(cfg, jax.random.key(0))
        st = init_decode_state(cfg, 2, 128)
        if cfg.modality in ("vision", "audio"):
            tok = jax.random.normal(jax.random.key(1), (2, 1, cfg.d_model),
                                    jnp.float32)
            logits, st2 = decode_step(params, cfg, None, st, embeds=tok)
        else:
            tok = jnp.ones((2, 1), jnp.int32)
            logits, st2 = decode_step(params, cfg, tok, st)
        assert int(st2.length) == 1
        assert not bool(jnp.isnan(logits).any())


class TestDecodeConsistency:
    """Decode step must reproduce teacher-forced forward logits."""

    @pytest.mark.parametrize("arch", ["chatglm3-6b", "mamba2-780m",
                                      "gemma2-2b", "zamba2-7b"])
    def test_decode_matches_forward(self, arch):
        cfg = small_cfg(arch)
        params = init_model(cfg, jax.random.key(0))
        B, S = 2, 16
        toks = jax.random.randint(jax.random.key(1), (B, S), 0,
                                  cfg.vocab_size)
        full = forward(params, cfg, tokens=toks).logits      # (B,S,V)
        st = init_decode_state(cfg, B, 64)
        outs = []
        for i in range(S):
            lg, st = decode_step(params, cfg, toks[:, i:i + 1], st)
            outs.append(lg[:, 0])
        dec = jnp.stack(outs, axis=1)
        err = float(jnp.max(jnp.abs(dec - full)))
        assert err < 2e-2, f"decode/forward mismatch {err}"


class TestNystromConfigs:
    def test_nystrom_attention_trains(self):
        cfg = small_cfg("phi4-mini-3.8b", attn_approx="nystrom_rls",
                        nystrom_landmarks=32)
        params = init_model(cfg, jax.random.key(0))
        b = _batch(cfg)
        l = loss_fn(params, cfg, b["tokens"], b["labels"])
        assert not bool(jnp.isnan(l))
        g = jax.grad(lambda p: loss_fn(p, cfg, b["tokens"], b["labels"]))(
            params)
        gn = jnp.sqrt(sum(jnp.sum(x ** 2) for x in jax.tree.leaves(g)))
        assert float(gn) > 0 and not bool(jnp.isnan(gn))

    def test_nystrom_decode_runs(self):
        cfg = small_cfg("chatglm3-6b", attn_approx="nystrom_rls",
                        nystrom_landmarks=16, rls_keep_recent=4)
        params = init_model(cfg, jax.random.key(0))
        st = init_decode_state(cfg, 2, 64)
        tok = jnp.ones((2, 1), jnp.int32)
        for _ in range(3):
            logits, st = decode_step(params, cfg, tok, st)
        assert not bool(jnp.isnan(logits).any())
