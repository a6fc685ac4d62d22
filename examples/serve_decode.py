"""Serving example: prefill a prompt, then batched greedy decode against
the KV/SSM cache — the same serve_step the decode_32k / long_500k
dry-runs lower, here on a reduced config.

    PYTHONPATH=src python examples/serve_decode.py --arch jamba-v0.1-52b
"""
import argparse

import jax
import jax.numpy as jnp

from repro import configs, models
from repro.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="h2o-danube-1.8b",
                    choices=configs.all_arch_ids())
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=24)
    ap.add_argument("--batch", type=int, default=4)
    args = ap.parse_args()

    cfg = configs.get(args.arch, smoke=True)
    params = models.init(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1),
                                (args.batch, args.prompt_len), 0,
                                cfg.vocab_size)
    cache_len = args.prompt_len + args.gen

    # prefill by decoding the prompt token-by-token (shape-stable cache);
    # a production server would run the batched prefill forward instead.
    decode = jax.jit(
        lambda p, t, c, i: models.decode_step(p, cfg, t, c, i),
        donate_argnums=(2,))
    cache = models.init_cache(cfg, args.batch, cache_len)
    tok = prompt[:, :1]
    out = [tok]
    for t in range(cache_len - 1):
        lg, cache = decode(params, tok, cache, jnp.int32(t))
        if t + 1 < args.prompt_len:
            tok = prompt[:, t + 1:t + 2]          # teacher-force the prompt
        else:
            tok = jnp.argmax(lg, -1).astype(jnp.int32)  # greedy
        out.append(tok)
    toks = jnp.concatenate(out, axis=1)
    print(f"{args.arch} (reduced): generated {args.gen} tokens x "
          f"{args.batch} sequences")
    for b in range(args.batch):
        seq = " ".join(str(int(x)) for x in toks[b, args.prompt_len:])
        print(f"  seq{b}: {seq}")


if __name__ == "__main__":
    main()
