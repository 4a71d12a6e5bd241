"""Per-architecture smoke tests (deliverable f): for each assigned arch,
instantiate the REDUCED same-family config, run one forward/train step and
one decode step on CPU, assert output shapes + no NaNs."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import all_arch_ids, get_config, get_smoke_config
from repro.data.pipeline import SyntheticTextConfig, make_lm_batch
from repro.models import init_params, lm
from repro.optim.base import SGD, apply_updates

ARCHS = all_arch_ids()
B, S = 2, 32


def _batch(cfg, key):
    tc = SyntheticTextConfig(vocab_size=cfg.vocab_size, seq_len=S)
    kw = {}
    if cfg.arch_type == "vlm":
        kw = dict(with_images=cfg.num_image_tokens, d_model=cfg.d_model,
                  dtype=cfg.jax_dtype)
    if cfg.arch_type == "audio":
        kw = dict(with_frames=cfg.num_audio_frames, d_model=cfg.d_model,
                  dtype=cfg.jax_dtype)
    return make_lm_batch(key, tc, B, **kw)


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_reduced_config(arch):
    cfg = get_smoke_config(arch)
    assert cfg.num_layers <= 4 and cfg.d_model <= 512
    assert cfg.num_experts <= 4
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    batch = _batch(cfg, key)

    # forward shapes + finite
    logits, aux = lm.forward(cfg, params, batch["tokens"],
                             image_embeds=batch.get("image_embeds"),
                             frames=batch.get("frames"))
    assert logits.shape == (B, S, cfg.padded_vocab)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))

    # one SGD train step decreases nothing pathological (finite loss + grads)
    def loss(p):
        return lm.loss_fn(cfg, p, batch)[0]

    l0, grads = jax.value_and_grad(loss)(params)
    assert bool(jnp.isfinite(l0))
    gn = sum(float(jnp.sum(jnp.square(g.astype(jnp.float32))))
             for g in jax.tree_util.tree_leaves(grads))
    assert jnp.isfinite(gn) and gn > 0

    opt = SGD(lr=0.1)
    upd, _ = opt.update(grads, opt.init(params))
    params2 = apply_updates(params, upd)
    l1 = float(loss(params2))
    assert jnp.isfinite(l1)


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_decode_step(arch):
    cfg = get_smoke_config(arch)
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    batch = _batch(cfg, key)
    image_kv = enc_kv = None
    if cfg.arch_type == "vlm":
        image_kv = lm.make_image_kv(cfg, params, batch["image_embeds"])
    if cfg.arch_type == "audio":
        enc_kv = lm.make_enc_kv(cfg, params, batch["frames"])
    cache = lm.init_cache(cfg, B, S, image_kv=image_kv, enc_kv=enc_kv)
    tok = batch["tokens"][:, 0]
    for t in range(3):
        logits, cache = lm.decode_step(cfg, params, cache, tok,
                                       jnp.int32(t))
        assert logits.shape == (B, cfg.padded_vocab)
        assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
        tok = jnp.argmax(logits, -1).astype(jnp.int32) % cfg.vocab_size


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_matches_assignment(arch):
    """The FULL configs carry the exact assigned numbers (never allocated
    here)."""
    cfg = get_config(arch)
    expected = {
        "mamba2-780m": (48, 1536, 50280),
        "deepseek-v2-lite-16b": (27, 2048, 102400),
        "starcoder2-3b": (30, 3072, 49152),
        "phi3.5-moe-42b-a6.6b": (32, 4096, 32064),
        "gemma3-12b": (48, 3840, 262144),
        "minitron-8b": (32, 4096, 256000),
        "zamba2-1.2b": (38, 2048, 32000),
        "llama-3.2-vision-11b": (40, 4096, 128256),
        "qwen1.5-110b": (80, 8192, 152064),
        "whisper-tiny": (4, 384, 51865),
    }[arch]
    assert (cfg.num_layers, cfg.d_model, cfg.vocab_size) == expected
    assert cfg.source  # every config cites its assignment bracket


def test_param_counts_plausible():
    """Full-config parameter counts are in the advertised ballpark."""
    expect = {"mamba2-780m": (0.6e9, 1.1e9),
              "starcoder2-3b": (2.5e9, 3.8e9),
              "deepseek-v2-lite-16b": (10e9, 20e9),
              "qwen1.5-110b": (90e9, 130e9),
              "whisper-tiny": (2e7, 1.2e8)}
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).param_count()
        assert lo <= n <= hi, (arch, n)
