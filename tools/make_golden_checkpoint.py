"""Train and export the golden-tiny checkpoint (the real-weights gate).

Every TPU bench so far ran random-init weights, so generation quality,
quantization quality, and the detokenizer's streaming behavior on a real
vocabulary were structurally unmeasurable (VERDICT r4 weak #3). This
script closes that: it trains the ``golden-tiny`` config (32k vendored
sentencepiece vocab) on the repo's own documentation with the
first-party train step, then exports a REAL HF-format checkpoint
(safetensors + config.json + tokenizer.model) that CI imports through
the production path (tests/test_real_weights_gate.py).

Usage::

    python tools/make_golden_checkpoint.py [--steps 300] \
        [--out tests/fixtures/golden_tiny]

Deterministic given the same corpus + seed.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def load_corpus(tokenizer) -> "np.ndarray":
    import numpy as np
    texts = []
    for path in sorted(glob.glob(os.path.join(REPO, "docs", "*.md"))):
        with open(path) as f:
            texts.append(f.read())
    ids = []
    for t in texts:
        ids.extend(int(i) for i in tokenizer.encode(t))
    return np.asarray(ids, np.int32)


def export_hf(params, cfg, out_dir: str) -> None:
    """Write the param tree as an HF llama checkpoint — the INVERSE of
    models/import_hf.py's key map, so the CI gate exercises the real
    import path (transpose back to (out, in), per-layer key names)."""
    import numpy as np
    from safetensors.numpy import save_file

    tensors: dict[str, np.ndarray] = {}

    def put(name, arr, transpose=False):
        # ascontiguousarray matters: np.asarray on a CPU jax array can
        # return a COLUMN-major view (XLA picks the layout), astype
        # preserves memory order ('K'), and safetensors writes the raw
        # buffer without normalizing — an F-order tensor lands on disk
        # with transposed bytes (debugged r5: the embed table came back
        # as a permutation of itself and NLL was random-level).
        a = np.ascontiguousarray(
            np.asarray(arr, np.float32).astype(np.float16))
        tensors[name] = np.ascontiguousarray(a.T) if transpose else a

    put("model.embed_tokens.weight", params["embed"])
    put("model.norm.weight", params["final_norm"])
    put("lm_head.weight", params["lm_head"], transpose=True)
    lp = params["layers"]
    for i in range(cfg.num_layers):
        pre = f"model.layers.{i}."
        put(pre + "input_layernorm.weight", lp["attn_norm"][i])
        put(pre + "post_attention_layernorm.weight", lp["mlp_norm"][i])
        put(pre + "self_attn.q_proj.weight", lp["wq"][i], transpose=True)
        put(pre + "self_attn.k_proj.weight", lp["wk"][i], transpose=True)
        put(pre + "self_attn.v_proj.weight", lp["wv"][i], transpose=True)
        put(pre + "self_attn.o_proj.weight", lp["wo"][i], transpose=True)
        put(pre + "mlp.gate_proj.weight", lp["w_gate"][i], transpose=True)
        put(pre + "mlp.up_proj.weight", lp["w_up"][i], transpose=True)
        put(pre + "mlp.down_proj.weight", lp["w_down"][i], transpose=True)
    os.makedirs(out_dir, exist_ok=True)
    save_file(tensors, os.path.join(out_dir, "model.safetensors"))
    with open(os.path.join(out_dir, "config.json"), "w") as f:
        json.dump({
            "architectures": ["LlamaForCausalLM"],
            "vocab_size": cfg.vocab_size,
            "hidden_size": cfg.hidden_size,
            "intermediate_size": cfg.intermediate_size,
            "num_hidden_layers": cfg.num_layers,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "max_position_embeddings": cfg.max_position_embeddings,
            "tie_word_embeddings": False,
            "_golden_tiny": True,
        }, f, indent=2)
    shutil.copy(
        os.path.join(REPO, "generativeaiexamples_tpu", "assets",
                     "tokenizer_32k.model"),
        os.path.join(out_dir, "tokenizer.model"))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=300)
    parser.add_argument("--batch", type=int, default=16)
    parser.add_argument("--seq", type=int, default=128)
    parser.add_argument("--lr", type=float, default=3e-3)
    parser.add_argument("--out", default=os.path.join(
        REPO, "tests", "fixtures", "golden_tiny"))
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.models.configs import get_model_config
    from generativeaiexamples_tpu.models.tokenizer import get_tokenizer
    from generativeaiexamples_tpu.training import make_train_step

    cfg = get_model_config("golden-tiny")
    tok = get_tokenizer(os.path.join(
        REPO, "generativeaiexamples_tpu", "assets", "tokenizer_32k.model"))
    corpus = load_corpus(tok)
    print(f"corpus: {len(corpus)} tokens")

    params = llama.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    optimizer = optax.adamw(args.lr)
    opt_state = optimizer.init(params)
    step = jax.jit(make_train_step(cfg, optimizer))

    rng = np.random.default_rng(0)
    B, S = args.batch, args.seq
    for i in range(args.steps):
        starts = rng.integers(0, len(corpus) - S - 1, size=B)
        tokens = np.stack([corpus[s:s + S] for s in starts])
        targets = np.stack([corpus[s + 1:s + S + 1] for s in starts])
        batch = {"tokens": jnp.asarray(tokens),
                 "targets": jnp.asarray(targets),
                 "mask": jnp.ones((B, S), jnp.int32)}
        params, opt_state, loss = step(params, opt_state, batch)
        if i % 50 == 0 or i == args.steps - 1:
            print(f"step {i}: loss {float(loss):.3f}")

    export_hf(params, cfg, args.out)
    size = sum(os.path.getsize(os.path.join(args.out, f))
               for f in os.listdir(args.out))
    print(f"exported {args.out} ({size / 1e6:.1f} MB), "
          f"final loss {float(loss):.3f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
