"""Train one of the assigned LM architectures (reduced config) end-to-end,
with the paper's lsh_softmax feature toggled on/off for comparison.

    PYTHONPATH=src python examples/train_lm.py --arch qwen3-0.6b --steps 40
"""
import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.configs import base as CB
from repro.launch.train import synth_batch, train_loop
from repro.models import lm, steps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--lsh-softmax", action="store_true")
    args = ap.parse_args()

    cfg = CB.reduced(CB.get(args.arch))
    print(f"arch={args.arch} family={cfg.family} (reduced) "
          f"lsh_softmax={args.lsh_softmax}")

    if not args.lsh_softmax:
        _, _, losses = train_loop(cfg, steps_n=args.steps, batch=8, seq=128)
        print(f"loss {losses[0]:.3f} → {losses[-1]:.3f}")
        return

    # paper-technique softmax: simLSH over output-embedding rows selects
    # the candidate vocabulary; signatures refresh every 10 steps
    from repro.models import lsh_softmax as LS
    cfg = dataclasses.replace(cfg, lsh_softmax=True, lsh_candidates=128)
    rng = np.random.default_rng(0)
    params = lm.init_params(cfg, jax.random.PRNGKey(0), model_shards=1)
    opt = steps.init_opt(cfg, params)
    step_fn = jax.jit(steps.make_train_step(cfg), donate_argnums=(0, 1))
    st = None
    for s in range(args.steps):
        b = synth_batch(rng, cfg, 8, 128)
        if s % 10 == 0:
            st = LS.refresh(lm.out_embedding(params, cfg),
                            jax.random.fold_in(jax.random.PRNGKey(7), s))
        b["cands"] = LS.candidates_for(
            st, b["labels"], jax.random.fold_in(jax.random.PRNGKey(9), s),
            n_cands=cfg.lsh_candidates)
        params, opt, aux = step_fn(params, opt, b)
        if s % 10 == 0 or s == args.steps - 1:
            print(f"step {s:4d} simLSH-softmax loss {float(aux['loss']):.3f}")


if __name__ == "__main__":
    compile_cache.enable()
    main()
