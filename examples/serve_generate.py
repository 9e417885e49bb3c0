"""Batched serving example: train a small SMILES seq2seq (MolMIM-class)
briefly, then serve a batch of requests — prefill + greedy decode with the
framework's KV-cache path (the same decode_step the serving engine runs).

    PYTHONPATH=src python examples/serve_generate.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.core.config import TrainConfig
from repro.data.dataset import MemmapTokenDataset, synthetic_smiles_sequences
from repro.data.tokenizer import SmilesTokenizer
from repro.models.model import build_model
from repro.training.loop import run_training


def main() -> None:
    cfg = get_smoke_config("molmim-65m")
    model = build_model(cfg)
    tok = SmilesTokenizer()
    print(f"arch={cfg.name} (enc-dec) vocab={tok.vocab_size}")

    # brief training so generations aren't pure noise
    seqs = synthetic_smiles_sequences(800, seed=0)
    enc = [np.asarray(tok.encode(s), np.int32) for s in seqs]
    ds = MemmapTokenDataset.write("/tmp/smiles/d", enc)

    def batches():
        rng = np.random.default_rng(0)
        while True:
            idx = rng.integers(0, len(ds), size=8)
            toks = np.zeros((8, 48), np.int32)
            for r, i in enumerate(idx):
                s = ds[int(i)][:48]
                toks[r, :len(s)] = s
            yield {"tokens": toks, "src_tokens": toks}

    tc = TrainConfig(global_batch=8, seq_len=48, total_steps=60,
                     learning_rate=3e-3, warmup_steps=5, decay_steps=5,
                     log_every=20)
    state, hist = run_training(model, tc, batches())

    # ---- serve a batch of 4 requests ----
    prompts = synthetic_smiles_sequences(4, seed=7)
    toks = jnp.asarray(tok.encode_batch(prompts, 24), jnp.int32)
    batch = {"tokens": toks[:, :8], "src_tokens": toks}
    prefill = jax.jit(lambda p, b: model.prefill(p, b, 48))
    decode = jax.jit(model.decode_step)
    logits, cache = prefill(state.params, batch)
    out = []
    t0 = time.time()
    for _ in range(16):
        nxt = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        out.append(nxt)
        logits, cache = decode(state.params, cache, nxt)
    dt = time.time() - t0
    gen = np.asarray(jnp.concatenate(out, axis=1))
    print(f"\nserved 4 requests, 16 tokens each, {4 * 16 / dt:.1f} tok/s")
    for i, p in enumerate(prompts):
        print(f"  prompt={p[:20]!r:24s} -> {tok.decode(gen[i])!r}")


if __name__ == "__main__":
    main()
