"""Batched serving driver: prefill a batch of prompts, then decode tokens.

On CPU with ``--reduced`` this demonstrates the end-to-end serving path of
any assigned arch (prefill -> KV/state cache -> token-by-token decode with
greedy sampling) and reports tokens/s. The production decode shapes
(decode_32k / long_500k) are lowered at pod scale by ``dryrun.py``.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import configs
from ..core.qat import DISABLED, QATConfig
from ..models import registry
from .compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama_1_1b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-tokens", type=int, default=32)
    ap.add_argument("--no-qat", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = configs.get(args.arch)
    if args.reduced:
        cfg = configs.reduced(cfg)
    model = registry.get_model(cfg)
    qcfg = DISABLED if args.no_qat else QATConfig()

    key = jax.random.PRNGKey(args.seed)
    params = model.init(key)
    B, T = args.batch, args.prompt_len
    total = T + args.gen_tokens
    batch = {"tokens": jax.random.randint(key, (B, T), 0, cfg.vocab)}
    if cfg.family == "encdec":
        batch["features"] = jax.random.normal(
            key, (B, cfg.encoder_len, cfg.d_model), jnp.float32
        )
    if cfg.n_patches:
        batch["patches"] = jax.random.normal(
            key, (B, cfg.n_patches, cfg.d_model), jnp.float32
        )

    # Decode from a fresh cache, replaying the prompt token-by-token, then
    # generating greedily — exercises the exact serving path.
    dstep = jax.jit(
        lambda p, c, t, i: model.decode_step(p, c, t, i, qcfg)
    )
    cache = model.init_cache(B, total)
    tok = batch["tokens"][:, 0]
    t0 = time.time()
    generated = []
    for i in range(total - 1):
        logits, cache = dstep(params, cache, tok, jnp.int32(i))
        if i + 1 < T:
            tok = batch["tokens"][:, i + 1]
        else:
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            generated.append(np.asarray(tok))
    dt = time.time() - t0
    toks_s = B * (total - 1) / dt
    print(f"arch={cfg.name} batch={B} steps={total-1} "
          f"tokens/s={toks_s:.1f} (CPU, interpret-grade numbers)")
    print("generated (first seq):", [int(g[0]) for g in generated][:16])


if __name__ == "__main__":
    main()
