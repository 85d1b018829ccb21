"""Batched serving launcher: prefill a prompt batch, then decode.

Runs a reduced assigned architecture end-to-end on CPU (the full configs
serve through the same code path on the production mesh — proven by the
decode-shape dry-runs).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b \
        --batch 4 --prompt-len 32 --gen 32
"""
from __future__ import annotations

import argparse
import functools
import time

import numpy as np


@functools.lru_cache(maxsize=None)
def _jitted_steps(bundle):
    """One (prefill, decode) jit pair per bundle.

    Building the wrappers inside ``main`` gave every invocation a fresh
    compilation cache (tracelint TL001); callers embedding this module
    (tests, notebooks) now reuse the compiled steps across calls.
    """
    import jax
    return jax.jit(bundle.prefill_step), jax.jit(bundle.decode_step)


def main():
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from repro import configs
    from repro.models import model_zoo

    cfg = configs.smoke_variant(configs.get(args.arch))
    bundle = model_zoo.build(cfg)
    params, _ = bundle.init(jax.random.PRNGKey(args.seed))

    B, P = args.batch, args.prompt_len
    key = jax.random.PRNGKey(args.seed + 1)
    prompts = jax.random.randint(key, (B, P), 0, cfg.vocab_size)

    prefill, decode = _jitted_steps(bundle)

    batch = {"tokens": prompts, "caches": bundle.make_cache(B, args.cache_len)}
    if cfg.encoder_layers:
        batch["audio_embeds"] = jnp.zeros((B, cfg.encoder_seq, cfg.d_model),
                                          jnp.bfloat16)
    elif cfg.frontend == "vision":
        batch["image_embeds"] = jnp.zeros((B, cfg.num_patch_tokens,
                                           cfg.d_model), jnp.bfloat16)

    t0 = time.time()
    logits, caches = prefill(params, batch)
    logits.block_until_ready()
    t_prefill = time.time() - t0

    def sample(logits, key):
        if args.temperature == 0.0:
            return jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        return jax.random.categorical(
            key, logits / args.temperature)[:, None].astype(jnp.int32)

    out = [sample(logits, key)]
    t0 = time.time()
    for t in range(args.gen - 1):
        key, sk = jax.random.split(key)
        pos = jnp.full((B, 1), P + t, jnp.int32)
        logits, caches = decode(params, {"token": out[-1], "pos": pos,
                                         "caches": caches})
        out.append(sample(logits, sk))
    jax.block_until_ready(out[-1])
    t_decode = time.time() - t0

    toks = np.concatenate([np.asarray(o) for o in out], axis=1)
    print(f"arch={cfg.name} batch={B} prompt={P} gen={args.gen}")
    print(f"prefill: {t_prefill*1e3:.1f} ms "
          f"({B*P/t_prefill:.0f} tok/s incl. compile)")
    print(f"decode:  {t_decode*1e3:.1f} ms "
          f"({B*(args.gen-1)/max(t_decode,1e-9):.0f} tok/s)")
    print("sample token ids[0]:", toks[0, :16])


if __name__ == "__main__":
    main()
