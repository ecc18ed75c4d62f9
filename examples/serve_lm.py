"""Batched serving example: prefill-by-steps + greedy decode with KV/state
caches across three architecture families (attention / SSM / hybrid).

    PYTHONPATH=src python examples/serve_lm.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_reduced  # noqa: E402
from repro.models.model import make_model  # noqa: E402
from repro.serve.engine import ServeSession  # noqa: E402
from repro.sharding.rules import make_rules  # noqa: E402
from repro.utils import enable_compile_cache  # noqa: E402


def main():
    enable_compile_cache()
    rules = make_rules(None)
    rng = np.random.default_rng(0)
    for arch in ("yi-6b", "rwkv6-1.6b", "zamba2-1.2b"):
        cfg = get_reduced(arch)
        model = make_model(cfg)
        params = model.init(jax.random.PRNGKey(1))
        batch, prompt_len, gen = 4, 8, 12
        session = ServeSession(model, params, rules, batch=batch,
                               cache_len=prompt_len + gen + 1)
        prompts = rng.integers(0, cfg.vocab_size, (batch, prompt_len),
                               dtype=np.int32)
        out = session.generate(prompts, steps=gen)
        assert out.shape == (batch, gen)
        assert (out >= 0).all() and (out < cfg.vocab_size).all()
        print(f"{arch:14s} generated {out.shape}: {out[0].tolist()}")
    print("serve_lm OK")


if __name__ == "__main__":
    main()
