"""Serving example: batched prefill + KV-cache decode through the public API.

    PYTHONPATH=src python examples/serve_lm.py [--arch gemma3-12b]

Runs the reduced config of the chosen family: prefill a batch of prompts,
then greedily decode new tokens — both phases as chunked scans through the
compiled run driver (DESIGN.md §10), not a per-token Python loop: the host
is out of the token loop entirely, and the generated tokens stream back as
a named metric trace.

``REPRO_EXAMPLE_ROUNDS`` overrides --new-tokens (the CI smoke path).
"""
import argparse
import os
import time
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.data.pipeline import SyntheticTextConfig, make_lm_batch
from repro.methods.driver import Driver
from repro.models import init_params, lm


class DecodeState(NamedTuple):
    """Driver-scannable serving state; ``t`` is the cache position (the
    driver also keys its round index off it)."""

    cache: Any
    tok: jax.Array                    # next token to feed (batch,)
    emitted: jax.Array                # token fed THIS step (the output)
    t: jax.Array


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-12b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--new-tokens", type=int,
                    default=int(os.environ.get("REPRO_EXAMPLE_ROUNDS", 16)))
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch)
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    total = args.prompt_len + args.new_tokens

    tc = SyntheticTextConfig(vocab_size=cfg.vocab_size,
                             seq_len=args.prompt_len)
    kw = {}
    if cfg.arch_type == "vlm":
        kw = dict(with_images=cfg.num_image_tokens, d_model=cfg.d_model,
                  dtype=cfg.jax_dtype)
    if cfg.arch_type == "audio":
        kw = dict(with_frames=cfg.num_audio_frames, d_model=cfg.d_model,
                  dtype=cfg.jax_dtype)
    batch = make_lm_batch(key, tc, args.batch, **kw)

    image_kv = enc_kv = None
    if cfg.arch_type == "vlm":
        image_kv = lm.make_image_kv(cfg, params, batch["image_embeds"])
    if cfg.arch_type == "audio":
        enc_kv = lm.make_enc_kv(cfg, params, batch["frames"])
    cache = lm.init_cache(cfg, args.batch, total, image_kv=image_kv,
                          enc_kv=enc_kv)

    def greedy(logits):
        return (jnp.argmax(logits, -1).astype(jnp.int32) % cfg.vocab_size)

    # prefill: step the decode path over the prompt positions (exercises
    # the cache-consistency guarantees tested in tests/test_lm_parity.py);
    # the prompt is static driver data, indexed by the in-scan position t
    def prefill_step(s: DecodeState, data) -> DecodeState:
        tok = jax.lax.dynamic_index_in_dim(data["tokens"], s.t, axis=1,
                                           keepdims=False)
        logits, cache = lm.decode_step(cfg, params, s.cache, tok, s.t)
        return DecodeState(cache=cache, tok=greedy(logits), emitted=tok,
                           t=s.t + 1)

    zeros_tok = jnp.zeros((args.batch,), jnp.int32)
    state = DecodeState(cache=cache, tok=zeros_tok, emitted=zeros_tok,
                        t=jnp.zeros((), jnp.int32))
    t0 = time.time()
    state, _ = Driver(prefill_step, data=batch).run(state, args.prompt_len)
    print(f"[serve] {cfg.name}: prefilled {args.batch}x{args.prompt_len} "
          f"tokens in {time.time()-t0:.2f}s")

    # decode: the state's own greedy token feeds back; the generated
    # sequence streams out as the named metric trace
    def decode_step(s: DecodeState, data) -> DecodeState:
        logits, cache = lm.decode_step(cfg, params, s.cache, s.tok, s.t)
        return DecodeState(cache=cache, tok=greedy(logits), emitted=s.tok,
                           t=s.t + 1)

    t0 = time.time()
    state, traces = Driver(
        decode_step,
        metrics={"token": lambda s, d: s.emitted}).run(state,
                                                       args.new_tokens)
    dt = time.time() - t0
    gen = jnp.transpose(traces["token"]).astype(jnp.int32)  # (batch, new)
    print(f"[serve] generated {args.new_tokens} tokens/seq in {dt:.2f}s "
          f"({args.batch*args.new_tokens/dt:.1f} tok/s)")
    print(f"[serve] sample row: {gen[0][:12].tolist()}")


if __name__ == "__main__":
    main()
