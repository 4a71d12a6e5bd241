"""The Nemotron-H stack (Mamba-2 with B/C groups, held-expert dropless MoE,
GQA without rotary embedding) against the benchmark's plain f32 reference
(``bench/ref_nemotron_h.py``) on seeded random weights at the smoke size:
pattern ``MEM*E``, d_model 128, 4 B/C groups, 16 experts of which 4 are
held, top 3."""
import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import gen_nemotron_h, ref_nemotron_h  # noqa: E402
from repro.configs import get_smoke_config  # noqa: E402
from repro.models import init_params, lm, moe, ssm  # noqa: E402
from repro.models.common import rms_norm  # noqa: E402

ARCH = "nemotron-3-nano-30b-a3b"
SEQ = 64


def _smoke(**kw):
    return dataclasses.replace(get_smoke_config(ARCH), dtype="float32",
                               **kw)


def _bench_config(cfg):
    """The benchmark configuration's keys for a program config."""
    full = json.loads((ROOT / "bench" / "configs"
                       / "nemotron3-nano.l7.e8.n1.chip1.json").read_text())
    return dict(full, hidden_size=cfg.d_model,
                num_hidden_layers=cfg.num_layers,
                hybrid_override_pattern=cfg.layer_pattern,
                vocab_size=cfg.vocab_size, mamba_num_heads=cfg.ssm_nheads,
                mamba_head_dim=cfg.ssm_headdim, n_groups=cfg.ssm_ngroups,
                ssm_state_size=cfg.ssm_state, chunk_size=cfg.ssd_chunk,
                published_n_routed_experts=cfg.num_experts,
                n_routed_experts=cfg.held_experts,
                expert_first=cfg.expert_first,
                num_experts_per_tok=cfg.experts_per_token,
                moe_intermediate_size=cfg.d_ff,
                moe_shared_expert_intermediate_size=cfg.shared_expert_ff,
                num_attention_heads=cfg.num_heads,
                num_key_value_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
                dtype=cfg.dtype)


def _batch(cfg, seed=1, seq=SEQ):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (2, seq + 1), 1,
                              cfg.vocab_size)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def test_smoke_config_is_the_issue_size():
    cfg = get_smoke_config(ARCH)
    assert (cfg.pattern, cfg.d_model, cfg.ssm_ngroups, cfg.num_experts,
            cfg.held_experts, cfg.experts_per_token) == \
        ("MEM*E", 128, 4, 16, 4, 3)


def test_program_layout_is_the_benchmark_generators():
    cfg = _smoke()
    want = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda k: gen_nemotron_h.params(k, _bench_config(cfg)),
                         jax.random.PRNGKey(0))
    assert want == got


def test_loss_and_grads_equal_the_f32_reference(monkeypatch):
    """Both in f32 on the CPU, the reference in blocks of 16 query
    positions (32 for the loss) so that its cross-block decays, causal
    masks and blocked loss all run at 64 tokens.  The program's chunked
    SSD, grouped products and attention order their sums differently from
    the reference's quadratic forms and dense expert loop, so the two
    agree to f32 round-off, about 1e-6 of each leaf: the loss to 1e-5
    relative and every gradient leaf to 1e-4 of its norm, room for the
    round-off of other seeds and machines, and far under what one wrong
    group, expert or mask gives."""
    monkeypatch.setattr(ref_nemotron_h, "SSD_BLOCK", 16)
    monkeypatch.setattr(ref_nemotron_h, "ATTN_BLOCK", 16)
    monkeypatch.setattr(ref_nemotron_h, "LOSS_BLOCK", 32)
    cfg = _smoke()
    bcfg = _bench_config(cfg)
    params = jax.jit(lambda k: gen_nemotron_h.params(k, bcfg))(
        jax.random.PRNGKey(3))
    batch = _batch(cfg)
    loss, grads = jax.value_and_grad(
        lambda p: lm.loss_fn(cfg, p, batch)[0])(params)
    rloss, rgrads = jax.value_and_grad(
        lambda p: ref_nemotron_h.batch_loss(p, batch, bcfg))(params)
    np.testing.assert_allclose(float(loss), float(rloss), rtol=1e-5)
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree_util.tree_leaves(rgrads)):
        gap = float(jnp.linalg.norm(g - r) / jnp.linalg.norm(r))
        assert gap < 1e-4, (jax.tree_util.keystr(path), gap)


def _moe_params(cfg, key):
    return jax.tree_util.tree_map(lambda a: a[0], init_params(cfg, key)["moe"])


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips holding experts 0-3, 4-7, 8-11 and 12-15 each compute
    their experts' part and the shared expert; the parts, with the shared
    expert counted once, are the layer that holds all 16."""
    whole = _smoke(experts_held=16)
    p = _moe_params(whole, jax.random.PRNGKey(5))
    x = jax.random.normal(jax.random.PRNGKey(6), (2, SEQ, whole.d_model))
    full, routed_full, _ = moe.moe_held(p, x, whole)
    total, routed = 0.0, []
    for first in range(0, 16, 4):
        cut = _smoke(experts_held=4, expert_first=first)
        part = dict(p, w_in=p["w_in"][first:first + 4],
                    w_out=p["w_out"][first:first + 4])
        y, r, dropped = moe.moe_held(part, x, cut)
        total = total + y
        routed.append(r)
        assert int(dropped) == 0
    shared = moe.mlp_apply({"w_in": p["shared_w_in"],
                            "w_out": p["shared_w_out"]}, x, "relu2")
    np.testing.assert_allclose(np.asarray(total - 3 * shared),
                               np.asarray(full), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.concatenate(routed),
                                  np.asarray(routed_full))
    assert int(routed_full.sum()) == 2 * SEQ * whole.experts_per_token


def test_dropless_when_every_token_picks_one_held_expert():
    """A router that sends every token to held expert 1 (far past any
    capacity factor's share: its column reads the inputs' common offset,
    which scores it 1 for every token): all of them are computed, none
    dropped."""
    cfg = _smoke()
    p = _moe_params(cfg, jax.random.PRNGKey(7))
    p = dict(p, router=p["router"].at[:, cfg.expert_first + 1].set(1.0))
    x = jax.random.normal(jax.random.PRNGKey(8), (2, SEQ, cfg.d_model)) + 2.0
    y, routed, dropped = moe.moe_held(p, x, cfg)
    assert int(routed[1]) == 2 * SEQ and int(dropped) == 0
    # the same layer, one dense pass per held expert
    xt = x.reshape(-1, cfg.d_model)
    s = jax.nn.sigmoid(xt @ p["router"])
    _, idx = jax.lax.top_k(s, cfg.experts_per_token)
    w = jnp.take_along_axis(s, idx, -1)
    w = w / w.sum(-1, keepdims=True) * cfg.routed_scale
    want = moe.mlp_apply({"w_in": p["shared_w_in"],
                          "w_out": p["shared_w_out"]}, xt, "relu2")
    for e in range(cfg.held_experts):
        col = jnp.sum(jnp.where(idx == cfg.expert_first + e, w, 0.0), -1)
        want = want + col[:, None] * moe.mlp_apply(
            {"w_in": p["w_in"][e], "w_out": p["w_out"][e]}, xt, "relu2")
    np.testing.assert_allclose(np.asarray(y.reshape(-1, cfg.d_model)),
                               np.asarray(want), rtol=1e-4, atol=1e-4)


def test_dropped_counts_assignments_outside_their_group():
    """The dropped count reads where each assignment's output comes from:
    rows sorted by expert give 0; expert 0's group cut to 2 rows (a
    capacity) leaves its third assignment, and expert 1's second, outside
    their groups."""
    groups = jnp.array([0, 0, 0, 1, 1, 2, 2])         # 2 = absent
    rows = jnp.arange(7)
    assert int(moe.dropped_assignments(jnp.array([3, 2, 2]), rows,
                                       groups)) == 0
    assert int(moe.dropped_assignments(jnp.array([2, 2, 3]), rows,
                                       groups)) == 2


def _ssd_inputs(G, S=48, H=8, P=4, N=5, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (2, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    b = jax.random.normal(ks[3], (2, S, G, N))
    c = jax.random.normal(ks[4], (2, S, G, N))
    return x, dt, A, b, c, jnp.ones((H,))


@pytest.mark.parametrize("G,chunk", [(2, 8), (4, 16), (8, 48)])
def test_grouped_ssd_equals_the_quadratic_form(G, chunk):
    """Head h reads group h // (H/G): the chunked scan equals y = (L o
    C_g B_g^T) (dt x) + D x computed whole."""
    x, dt, A, b, c, D = _ssd_inputs(G)
    y, _ = ssm.ssd_chunked(x, dt, A, b, c, D, chunk)
    H = x.shape[2]
    bh = jnp.repeat(b, H // G, axis=2)
    ch = jnp.repeat(c, H // G, axis=2)
    a = dt * A
    cs = jnp.cumsum(a, 1)
    S = x.shape[1]
    diff = cs[:, :, None, :] - cs[:, None, :, :]           # (B, q, k, H)
    causal = jnp.tril(jnp.ones((S, S), bool))[None, :, :, None]
    L = jnp.where(causal, jnp.exp(jnp.where(causal, diff, 0.0)), 0.0)
    scores = jnp.einsum("bqhn,bkhn->bqkh", ch, bh)
    want = jnp.einsum("bqkh,bkhp->bqhp", L * scores, x * dt[..., None]) \
        + x * D[None, None, :, None]
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_grouped_ssd_with_equal_groups_is_the_one_group_scan():
    x, dt, A, b, c, D = _ssd_inputs(1)
    y1, _ = ssm.ssd_chunked(x, dt, A, b[:, :, 0], c[:, :, 0], D, 16)
    y4, _ = ssm.ssd_chunked(x, dt, A, jnp.repeat(b, 4, 2),
                            jnp.repeat(c, 4, 2), D, 16)
    np.testing.assert_allclose(np.asarray(y4), np.asarray(y1),
                               rtol=1e-5, atol=1e-5)


def test_grouped_ssd_carries_its_state_across_a_split():
    """With 4 groups, the scan over the second half from the first half's
    final state gives the whole sequence's output and final state."""
    x, dt, A, b, c, D = _ssd_inputs(4)
    y, s = ssm.ssd_chunked(x, dt, A, b, c, D, 8)
    y1, s1 = ssm.ssd_chunked(x[:, :24], dt[:, :24], A, b[:, :24],
                             c[:, :24], D, 8)
    y2, s2 = ssm.ssd_chunked(x[:, 24:], dt[:, 24:], A, b[:, 24:],
                             c[:, 24:], D, 8, s1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s),
                               rtol=1e-4, atol=1e-4)


def _parent_ssd(x, dt, A, b, c, D, chunk):
    """The one-group chunked SSD as the program computed it before B/C
    groups (b/c: (B,S,N))."""
    Bb, S, H, P = x.shape
    N = b.shape[-1]
    nc = S // chunk
    f32 = jnp.float32
    xc = (x * dt[..., None]).astype(f32).reshape(Bb, nc, chunk, H, P)
    ac = (dt * A[None, None, :]).astype(f32).reshape(Bb, nc, chunk, H)
    bc = b.astype(f32).reshape(Bb, nc, chunk, N)
    cc = c.astype(f32).reshape(Bb, nc, chunk, N)
    acs = jnp.cumsum(ac, 2)
    L = jnp.exp(ssm._segsum(jnp.moveaxis(ac, -1, -2)))
    scores = jnp.einsum("bnqs,bnks->bnqk", cc, bc)
    y_diag = jnp.einsum("bnhqk,bnqk,bnkhp->bnqhp", L, scores, xc)
    decay_end = jnp.exp(acs[:, :, -1:, :] - acs)
    chunk_states = jnp.einsum("bnks,bnkh,bnkhp->bnhsp", bc, decay_end, xc)
    decay_chunk = jnp.exp(acs[:, :, -1, :])

    def scan_fn(s, inp):
        st, dk = inp
        return s * dk[..., None, None] + st, s

    _, prev = jax.lax.scan(
        scan_fn, jnp.zeros((Bb, H, N, P), f32),
        (jnp.moveaxis(chunk_states, 1, 0), jnp.moveaxis(decay_chunk, 1, 0)))
    prev = jnp.moveaxis(prev, 0, 1)
    y_off = jnp.einsum("bnqs,bnqh,bnhsp->bnqhp", cc, jnp.exp(acs), prev)
    y = (y_diag + y_off).reshape(Bb, S, H, P)
    return (y + x.astype(f32) * D[None, None, :, None]).astype(x.dtype)


def _parent_mixer(p, x, cfg):
    """The one-group Mamba2 mixer as the program computed it before B/C
    groups: one B/C slice, the gated norm over all of d_inner."""
    B, S, _ = x.shape
    H, P, N = cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_state
    z = jnp.einsum("bsd,dhp->bshp", x, p["w_z"])
    xbc = jnp.einsum("bsd,dc->bsc", x, p["w_xbc"])
    dt = jax.nn.softplus(jnp.einsum("bsd,dh->bsh", x, p["w_dt"])
                         + p["dt_bias"])
    xbc = ssm._conv1d_prefill(xbc, p["conv_w"], p["conv_b"])
    xs = xbc[..., :H * P].reshape(B, S, H, P)
    bmat = xbc[..., H * P:H * P + N]
    cmat = xbc[..., H * P + N:]
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    y = _parent_ssd(xs, dt, A, bmat, cmat, p["D"], min(cfg.ssd_chunk, S))
    y = y * jax.nn.silu(z)
    y = rms_norm(y.reshape(B, S, H * P), p["norm"], cfg.norm_eps)
    return jnp.einsum("bsc,cd->bsd", y, p["w_out"])


def test_one_group_mixer_is_bit_equal_to_the_parent_formula():
    cfg = get_smoke_config("mamba2-780m")
    p = jax.tree_util.tree_map(lambda a: a[0],
                               init_params(cfg, jax.random.PRNGKey(2))["layers"])
    x = jax.random.normal(jax.random.PRNGKey(4), (2, SEQ, cfg.d_model),
                          cfg.jax_dtype)
    got = jax.jit(lambda p, x: ssm.mamba_mixer_prefill(p, x, cfg))(p, x)
    want = jax.jit(lambda p, x: _parent_mixer(p, x, cfg))(p, x)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_grouped_gated_norm_normalises_each_group():
    """With 4 groups, scaling one group's channels of y leaves the other
    groups' normalised output as it was."""
    y = jax.random.normal(jax.random.PRNGKey(9), (1, 3, 4, 16))
    w = jnp.zeros((4, 16))
    a = rms_norm(y, w)
    b = rms_norm(y.at[:, :, 0].multiply(10.0), w)
    np.testing.assert_allclose(np.asarray(a[:, :, 1:]),
                               np.asarray(b[:, :, 1:]), rtol=1e-6)


def test_attention_layers_take_no_rotary_embedding():
    """The attention layer is plain causal GQA, scores q.k / sqrt(hd) with
    no rotary embedding; the same weights with a rotary embedding give
    another output."""
    from repro.models.attention import gqa_prefill
    cfg = _smoke()
    assert not cfg.use_rope
    p = jax.tree_util.tree_map(
        lambda a: a[0], init_params(cfg, jax.random.PRNGKey(2))["attn"])
    S = 16
    x = jax.random.normal(jax.random.PRNGKey(3), (1, S, cfg.d_model))
    pos = jnp.arange(S)[None]
    R = cfg.num_heads // cfg.num_kv_heads
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.repeat(jnp.einsum("bsd,dgk->bsgk", x, p["wk"]), R, axis=2)
    v = jnp.repeat(jnp.einsum("bsd,dgk->bsgk", x, p["wv"]), R, axis=2)
    logits = jnp.einsum("bqhk,bshk->bhqs", q, k) / jnp.sqrt(cfg.head_dim)
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), -1)
    want = jnp.einsum("bshk,hkd->bsd",
                      jnp.einsum("bhqs,bshk->bqhk", probs, v), p["wo"])
    got = gqa_prefill(p, x, pos, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    roped = gqa_prefill(p, x, pos, dataclasses.replace(cfg, use_rope=True))
    assert not np.allclose(np.asarray(roped), np.asarray(want), atol=1e-3)


def test_dasha_chunk_through_make_method_and_driver(capsys):
    """One node, the smoke config, through the trainer: every leaf's mask
    drawn inside the keyed fused kernel, the leaves updated in their own
    layout as :func:`kernel_layout_count` counts them, the chip share and
    no dropped token printed at each log."""
    from repro.compress.treelevel import kernel_layout_count
    from repro.launch import train
    run = train.main(["--arch", ARCH, "--steps", "2", "--log-every", "2",
                      "--seq", "32", "--batch", "1", "--nodes", "1",
                      "--server-opt", "sgd", "--use-kernel", "--layers", "5",
                      "--experts", "4", "--vocab", "256"])
    out = capsys.readouterr().out
    leaves = len(jax.tree_util.tree_leaves(run.state.x))
    assert f"mask draw: in kernel {leaves}/{leaves} leaves" in out
    per_node = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct((1,) + x.shape, jnp.float32),
        run.state.x)
    assert f"node update: {kernel_layout_count(per_node)}\n" in out
    assert "chip share: layers 5/5 experts 4/16 vocab rows 256/512" in out
    assert "dropped=0" in out
    rec = run.log[-1]
    assert rec["dropped"] == 0 and np.isfinite(rec["loss"])
    assert sum(map(sum, rec["expert_tokens"])) > 0
    assert int(run.state.t) == 2
