"""End-to-end LM training driver: trains a small llama-family model for a
few hundred steps on the synthetic bigram pipeline, with checkpointing and a
mid-run restart, and verifies the loss actually drops.

    PYTHONPATH=src python examples/train_lm.py [--steps 200]
"""
import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.ckpt import CheckpointManager  # noqa: E402
from repro.configs import get_reduced  # noqa: E402
from repro.data.tokens import TokenPipeline  # noqa: E402
from repro.models.model import make_model  # noqa: E402
from repro.sharding.rules import make_rules  # noqa: E402
from repro.train.loop import init_train_state, make_train_step  # noqa: E402
from repro.train.optimizer import OptConfig  # noqa: E402
from repro.utils import enable_compile_cache  # noqa: E402


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--arch", default="yi-6b")
    args = ap.parse_args()

    cfg = get_reduced(args.arch)
    model = make_model(cfg)
    rules = make_rules(None)
    opt = OptConfig(lr=3e-3, warmup_steps=10, total_steps=args.steps)
    step_fn = jax.jit(make_train_step(model, opt, rules))
    pipe = TokenPipeline(cfg.vocab_size, seq_len=32, global_batch=8, seed=0)

    state = init_train_state(model, jax.random.PRNGKey(0))
    losses = []
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        half = args.steps // 2
        for i in range(half):
            toks, tgt = pipe.batch_at(i)
            state, m = step_fn(state, {"tokens": jnp.asarray(toks),
                                       "targets": jnp.asarray(tgt)})
            losses.append(float(m["loss"]))
            if (i + 1) % 20 == 0:
                print(f"step {i+1:4d} loss {losses[-1]:.4f}", flush=True)
        mgr.save(jax.tree_util.tree_map(np.asarray, state), step=half)
        print(f"-- checkpoint at step {half}; simulating restart --")

        # restart from scratch, restore, continue
        state2 = init_train_state(model, jax.random.PRNGKey(0))
        got_step, restored = mgr.restore_into(
            jax.tree_util.tree_map(np.asarray, state2))
        state2 = jax.tree_util.tree_map(jnp.asarray, restored)
        for i in range(got_step, args.steps):
            toks, tgt = pipe.batch_at(i)
            state2, m = step_fn(state2, {"tokens": jnp.asarray(toks),
                                         "targets": jnp.asarray(tgt)})
            losses.append(float(m["loss"]))
            if (i + 1) % 20 == 0:
                print(f"step {i+1:4d} loss {losses[-1]:.4f}", flush=True)

    first, last = np.mean(losses[:10]), np.mean(losses[-10:])
    print(f"mean loss first 10 steps: {first:.4f} -> last 10: {last:.4f}")
    assert last < first - 0.3, "loss did not decrease"
    print("train_lm OK")


if __name__ == "__main__":
    main()
