"""Serving launcher: prefill a batch of prompts, then lock-step decode.

``python -m repro.launch.serve --arch falcon-mamba-7b --smoke --tokens 32``

Uses the same jit_prefill_step / jit_decode_step builders the multi-pod
dry-run lowers, on the host mesh.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

import repro.configs as C
from repro.distributed.serving import jit_decode_step, jit_prefill_step
from repro.launch.mesh import make_host_mesh
from repro.models.model import init_params


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", required=True, choices=C.list_archs())
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--tokens", type=int, default=16)
    p.add_argument("--temperature", type=float, default=0.0)
    args = p.parse_args()

    cfg = (C.get_smoke_config(args.arch) if args.smoke
           else C.get_config(args.arch))
    mesh = make_host_mesh()
    b, s = args.batch, args.prompt_len
    max_seq = s + args.tokens

    with jax.set_mesh(mesh):
        params = init_params(cfg, jax.random.PRNGKey(0))
        if cfg.embed_input:
            inputs = {"tokens": jax.random.randint(
                jax.random.PRNGKey(1), (b, s), 0, cfg.vocab)}
        else:
            inputs = {"embeds": jax.random.normal(
                jax.random.PRNGKey(1), (b, s, cfg.d_model), cfg.jnp_dtype)}
        batch_sds = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), inputs)

        # prefill once, directly at the serving cache width
        from repro.models.model import prefill as _prefill
        t0 = time.time()
        prefill_fn = jax.jit(lambda p, i: _prefill(
            cfg, p, tokens=i.get("tokens"), embeds=i.get("embeds"),
            max_seq=max_seq))
        logits, cache = prefill_fn(params, inputs)
        print(f"prefill({b}x{s}): {time.time() - t0:.2f}s "
              f"logits {logits.shape}")
        decode_fn, _, _ = jit_decode_step(cfg, mesh, b, max_seq)

        key = jax.random.PRNGKey(2)
        out_tokens = []
        t0 = time.time()
        next_tok = jnp.argmax(logits, axis=-1)
        for i in range(args.tokens):
            if args.temperature > 0:
                key, sub = jax.random.split(key)
                next_tok = jax.random.categorical(
                    sub, logits / args.temperature, axis=-1)
            step_inputs = ({"tokens": next_tok} if cfg.embed_input else
                           {"embeds": jnp.zeros((b, 1, cfg.d_model),
                                                cfg.jnp_dtype)})
            logits, cache = decode_fn(params, cache, step_inputs)
            next_tok = jnp.argmax(logits, axis=-1)
            out_tokens.append(next_tok)
        dt = time.time() - t0
        toks = jnp.stack(out_tokens, axis=1)
        print(f"decoded {args.tokens} tokens x {b} seqs in {dt:.2f}s "
              f"({args.tokens * b / dt:.1f} tok/s)")
        print("sample token ids:", toks[0][:16].tolist())


if __name__ == "__main__":
    main()
