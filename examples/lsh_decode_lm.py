"""LSH-decode: the paper's technique inside an LM serving loop.

    PYTHONPATH=src python examples/lsh_decode_lm.py

Trains a small qwen3-family model for a few steps (so the unembedding has
non-trivial geometry), builds a RANGE-LSH index over the vocabulary, and
greedy-decodes with approximate top-1 token search — comparing tokens and
probe budget against exact decoding.
"""

import jax
import jax.numpy as jnp

from repro.configs.base import get_config
from repro.data.tokens import SyntheticCorpus
from repro.launch import serve
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.launch.train import TrainHParams, init_state, make_train_step
from repro.models import lm_head


def main() -> None:
    enable_compile_cache()
    cfg = get_config("qwen3_0_6b").reduced()
    mesh = make_local_mesh()
    hp = TrainHParams(lr=1e-3, warmup=5, total_steps=30)
    state = init_state(jax.random.PRNGKey(0), cfg)
    step_fn = make_train_step(cfg, mesh, hp)
    corpus = SyntheticCorpus(cfg.vocab, 32)
    for s in range(20):
        batch = dict(corpus.sample(s, 0, 8)._asdict())
        state, metrics = step_fn(state, batch, jnp.asarray(s, jnp.int32))
    print(f"trained 20 steps, loss {float(metrics['loss']):.3f}")
    params = state.params

    unembed = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    vidx = lm_head.build_vocab_index(unembed, jax.random.PRNGKey(1),
                                     code_len=64, num_ranges=16)
    norms = jnp.linalg.norm(unembed.T.astype(jnp.float32), axis=1)
    print(f"vocab norms: max/median = "
          f"{float(jnp.max(norms) / jnp.median(norms)):.2f}")

    prompts = jax.random.randint(jax.random.PRNGKey(2), (4, 8), 0,
                                 cfg.vocab)
    exact = serve.BatchedServer(cfg, params, mesh, max_seq=32)
    out_exact = exact.generate(prompts, steps=8)
    for probe in (64, 256):
        lsh = serve.BatchedServer(cfg, params, mesh, max_seq=32,
                                  lsh_decode=True, vocab_index=vidx,
                                  num_probe=probe)
        out_lsh = lsh.generate(prompts, steps=8)
        agree = float(jnp.mean((out_lsh == out_exact).astype(jnp.float32)))
        print(f"LSH-decode probing {probe}/{cfg.padded_vocab} vocab rows: "
              f"token agreement {agree:.2f}")


if __name__ == "__main__":
    main()
