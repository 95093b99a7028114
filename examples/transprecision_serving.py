"""Serving example: prefill a batch of prompts, then decode with a
transprecision KV cache (the paper's storage-format knob applied to the
dominant serving memory term).

Decoding runs as ONE compiled ``lax.scan`` (``Model.generate``) — the whole
generation is a single XLA dispatch; with ``--decode-backend pallas`` the
per-step attention additionally runs the fused in-kernel KV-dequant Pallas
kernel (kernels/decode_attention.py).

Runs a reduced config on CPU; the same code path lowers the decode_32k /
long_500k dry-run cells on the production meshes.

Run:  PYTHONPATH=src python examples/transprecision_serving.py [--arch gemma2-9b]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.registry import build_model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-9b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=48)
    ap.add_argument("--gen", type=int, default=24)
    ap.add_argument("--policy", default="tp_bf16")
    ap.add_argument("--decode-backend", choices=("dense", "pallas"),
                    default="dense")
    args = ap.parse_args()

    model = build_model(args.arch, policy=args.policy, reduced=True)
    model = model.with_cfg(decode_backend=args.decode_backend)
    cfg = model.cfg
    params = model.init(jax.random.key(0))
    max_len = args.prompt_len + args.gen

    prompts = jax.random.randint(jax.random.key(1),
                                 (args.batch, args.prompt_len), 0, cfg.vocab)

    prefill = jax.jit(lambda p, t: model.prefill(p, t, max_len=max_len))
    gen_fn = jax.jit(lambda p, t: model.generate(
        p, t, gen_len=args.gen, max_len=max_len)[0])

    t0 = time.time()
    logits, _ = prefill(params, prompts)
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0

    jax.block_until_ready(gen_fn(params, prompts))   # compile the scan
    t0 = time.time()
    gen = np.asarray(jax.block_until_ready(gen_fn(params, prompts)))
    t_dec = time.time() - t0

    kv_fmt = model.policy.kv_fmt.name if model.policy.kv_fmt else "param fmt"
    print(f"arch {cfg.name}: prefill {args.batch}x{args.prompt_len} in "
          f"{t_prefill*1e3:.0f} ms; one-dispatch scan generated "
          f"{args.gen} tokens/row in {t_dec*1e3:.0f} ms "
          f"({args.gen*args.batch/t_dec:.1f} tok/s on CPU, prefill incl.)")
    print(f"KV cache format: {kv_fmt} (policy '{model.policy.name}', "
          f"decode backend {cfg.decode_backend})")
    print("generated ids (row 0):", gen[0].tolist())
    assert gen.shape == (args.batch, args.gen)
    assert int(gen.max()) < cfg.vocab
    assert np.array_equal(
        gen[:, 0], np.asarray(jnp.argmax(logits[:, -1], -1)))


if __name__ == "__main__":
    main()
