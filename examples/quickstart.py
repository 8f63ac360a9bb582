"""Quickstart: train a tiny LM end to end on CPU in ~2 minutes.

  PYTHONPATH=src python examples/quickstart.py
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent / "src"))

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.data import DataConfig, SyntheticTokenPipeline
from repro.jax_cache import enable_compile_cache
from repro.models import build_model, count_params
from repro.train import OptConfig, TrainConfig, init_train_state, make_train_step


def main() -> None:
    enable_compile_cache()
    cfg = get_config("llama3.2-1b", smoke=True)
    api = build_model(cfg)
    state = init_train_state(api, jax.random.PRNGKey(0))
    print(f"model: {cfg.name}  params: {count_params(state['params']):,}")

    tcfg = TrainConfig(opt=OptConfig(lr=2e-3, warmup_steps=10,
                                     total_steps=100))
    step = jax.jit(make_train_step(api, tcfg), donate_argnums=(0,))
    data = SyntheticTokenPipeline(DataConfig(vocab=cfg.vocab, seq_len=64,
                                             global_batch=8, seed=0))
    first = None
    for i in range(60):
        batch = {"tokens": jnp.asarray(data.batch_at(i)["tokens"])}
        state, m = step(state, batch)
        if first is None:
            first = float(m["loss"])
        if i % 10 == 0:
            print(f"step {i:3d}  loss {float(m['loss']):.4f}  "
                  f"lr {float(m['lr']):.2e}")
    final = float(m["loss"])
    print(f"loss {first:.3f} -> {final:.3f} "
          f"({'LEARNING' if final < first - 0.2 else 'check config'})")


if __name__ == "__main__":
    main()
