"""Train a ~100M-class LM for a few hundred steps end to end, with the
paper's machinery integrated:

  * --stratified-dp : assign data shards to DP ranks with the paper's
    landmark/stratum partitioner (repro.data.stratified);
  * --odm-head      : after LM training, fit an ODM classifier head on
    pooled hidden states via the SODM solver (integration point #1).

    PYTHONPATH=src python examples/train_lm.py --steps 200
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro import compile_cache
from repro import configs
from repro.data import lm as lmdata
from repro.models import model as M
from repro.train import steps as steps_mod


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--odm-head", action="store_true")
    args = ap.parse_args()

    cfg = configs.get_smoke(args.arch)
    params, _ = M.init_params(jax.random.PRNGKey(0), cfg)
    state = steps_mod.TrainState.create(params, use_ef=False)
    import dataclasses
    from repro.optim import adamw
    tc = steps_mod.TrainConfig(optimizer=adamw.AdamWConfig(
        lr=1e-3, warmup_steps=20, total_steps=args.steps))
    step = jax.jit(steps_mod.make_train_step(cfg, tc))
    dc = lmdata.LMDataConfig(vocab=cfg.vocab, seq_len=args.seq_len,
                             global_batch=args.batch)

    t0 = time.time()
    first = last = None
    for i in range(args.steps):
        state, mets = step(state, lmdata.batch_at(dc, i))
        if i == 0:
            first = float(mets["loss"])
        last = float(mets["loss"])
        if i % 25 == 0:
            print(f"step {i:4d} loss {last:.4f} ({time.time() - t0:.0f}s)",
                  flush=True)
    print(f"loss {first:.3f} -> {last:.3f} over {args.steps} steps")

    if args.odm_head:
        # integration point: ODM margin-distribution classifier on pooled
        # features, trained through the unified API
        from repro.api import ODMEstimator, ProblemSpec
        from repro.core import sodm
        print("fitting ODM head on pooled hidden states...")
        B, S, n = 8, args.seq_len, 32
        feats, labels = [], []
        for i in range(n):
            b = lmdata.batch_at(dc, 1000 + i)
            logits, _ = M.logits_fn(state["params"], b, cfg)
            pooled = jnp.mean(logits, axis=1)          # (B, V) proxy feature
            feats.append(pooled[:, :64])
            # synthetic binary target: does the sequence end high-token?
            labels.append(jnp.sign(b["tokens"][:, -1] - cfg.vocab // 2 + 0.5))
        xf = jnp.concatenate(feats).astype(jnp.float32)
        yf = jnp.concatenate(labels).astype(jnp.float32)
        Mn = xf.shape[0] - xf.shape[0] % 8
        xf, yf = xf[:Mn], yf[:Mn]
        est = ODMEstimator(
            ProblemSpec.create("rbf", gamma=0.5, lam=10.0),
            cfg=sodm.SODMConfig(p=2, levels=2, n_landmarks=4))
        est.fit(xf, yf, jax.random.PRNGKey(1))
        print(f"ODM head train accuracy: {est.score(xf, yf):.3f}")


if __name__ == "__main__":
    compile_cache.enable()
    main()
