"""End-to-end driver: train the paper's KWS model for a few hundred
steps on the synthetic GSCD corpus, with the full production substrate —
QAT (8-bit weights / 14-bit activations), AdamW + ReduceLROnPlateau
(the paper's recipe), periodic checkpointing with resume, straggler
monitoring, and optional data-parallel training with int8-compressed
gradient all-reduce.

  PYTHONPATH=src python examples/train_kws.py [--steps 300] [--resume]
  PYTHONPATH=src python examples/train_kws.py --dp 8 --compress-grads
      (runs 8-way data-parallel on fake devices, compressed psums)
"""

import argparse
import os
import sys
import time

sys.path.insert(0, "src")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--n-per-class", type=int, default=24)
    ap.add_argument("--ckpt-dir", default="/tmp/kws_ckpt")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--dp", type=int, default=0,
                    help="data-parallel ways (fake devices)")
    ap.add_argument("--compress-grads", action="store_true")
    args = ap.parse_args()
    if args.dp:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.dp}"
        )

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import quant
    from repro.core.fex import FExConfig, FExNormStats
    from repro.core.gru import GRUConfig, gru_classifier_forward, init_gru_classifier
    from repro.core.pipeline import KWSPipeline, KWSPipelineConfig
    from repro.data.gscd import CLASSES, make_dataset
    from repro.distributed.fault_tolerance import (
        CheckpointManager, CheckpointPolicy, StragglerMonitor)
    from repro.training.optimizer import (
        AdamWConfig, ReduceLROnPlateau, adamw_update, init_opt_state)

    print("== synthesizing corpus ==")
    train = make_dataset(args.n_per_class, seed=0, unknown_split="train")
    test = make_dataset(max(args.n_per_class // 3, 4), seed=1,
                        unknown_split="test")
    fcfg = FExConfig()

    print("== extracting features (frontend='software') ==")
    pipe = KWSPipeline(KWSPipelineConfig(fex=fcfg))
    raw_tr = pipe.record_features(train["audio"])
    raw_te = pipe.record_features(test["audio"])
    log_tr = quant.log_compress_lut(jnp.asarray(raw_tr), 12, 10)
    stats = FExNormStats(
        mu=log_tr.reshape(-1, 16).mean(0),
        sigma=log_tr.reshape(-1, 16).std(0) + 1e-3,
    )
    pipe = KWSPipeline(KWSPipelineConfig(fex=fcfg), norm_stats=stats)

    def normalize(raw):
        return np.asarray(pipe.features_from_raw(jnp.asarray(raw)))

    ftr, fte = normalize(raw_tr), normalize(raw_te)

    gcfg = GRUConfig()  # QAT on by default (8-bit w / Q6.8 act)
    params = init_gru_classifier(jax.random.PRNGKey(0), gcfg)
    ocfg = AdamWConfig(lr=1e-3, weight_decay=0.01)
    opt = init_opt_state(params, ocfg)
    sched = ReduceLROnPlateau(1e-3, 0.8, 3, 5e-4)
    ckpt = CheckpointManager(CheckpointPolicy(
        args.ckpt_dir, every_steps=100, async_save=True))
    monitor = StragglerMonitor()
    start_step = 0
    if args.resume:
        try:
            (params, opt), start_step = ckpt.restore_latest((params, opt))
            print(f"resumed from step {start_step}")
        except FileNotFoundError:
            print("no checkpoint found; starting fresh")

    def loss_fn(p, fv, y):
        logits = gru_classifier_forward(p, fv, gcfg)[:, -1, :]
        logz = jax.nn.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, y[:, None], -1)[:, 0]
        return jnp.mean(logz - gold)

    if args.dp:
        from jax.sharding import PartitionSpec as P

        from jax import shard_map

        from repro.distributed.collectives import (
            compressed_psum_with_error_feedback, init_residual)

        mesh = jax.make_mesh((args.dp,), ("data",))
        residual = init_residual(params) if args.compress_grads else None

        def dp_grads(p, fv, y, r):
            l, g = jax.value_and_grad(loss_fn)(p, fv, y)
            if args.compress_grads:
                g, r = compressed_psum_with_error_feedback(g, r, "data")
            else:
                g = jax.tree.map(
                    lambda t: jax.lax.pmean(t, "data"), g)
            return jax.lax.pmean(l, "data"), g, r

        in_specs = (P(), P("data"), P("data"),
                    P() if not args.compress_grads else P())
        print(f"== {args.dp}-way data parallel"
              f"{' + int8 compressed grads' if args.compress_grads else ''} ==")

    @jax.jit
    def step(p, o, fv, y, lr, r):
        if args.dp:
            l, g, r = shard_map(
                dp_grads, mesh=mesh,
                in_specs=(P(), P("data"), P("data"), P()),
                out_specs=(P(), P(), P()),
            )(p, fv, y, r)
        else:
            l, g = jax.value_and_grad(loss_fn)(p, fv, y)
        p, o, _ = adamw_update(p, g, o, ocfg, lr)
        return p, o, l, r

    residual = (
        jax.tree.map(lambda t: jnp.zeros(t.shape, jnp.float32), params)
    )
    rng = np.random.default_rng(0)
    n = len(train["label"])
    lr = sched.lr
    print(f"== training {args.steps} steps ==")
    t0 = time.time()
    losses = []
    for it in range(start_step, args.steps):
        sl = rng.choice(n, args.batch, replace=False)
        with monitor.timed(it):
            params, opt, loss, residual = step(
                params, opt, jnp.asarray(ftr[sl]),
                jnp.asarray(train["label"][sl]), lr, residual)
        losses.append(float(loss))
        if (it + 1) % 20 == 0:
            lr = sched.step(float(np.mean(losses[-20:])))
            print(f"  step {it + 1:4d} loss {np.mean(losses[-20:]):.4f} "
                  f"lr {lr:.2e}")
        ckpt.maybe_save(it + 1, (params, opt))
    ckpt.wait()
    print(f"trained in {time.time() - t0:.0f}s; "
          f"stragglers flagged: {len(monitor.events)}")

    @jax.jit
    def logits_fn(fv):
        return gru_classifier_forward(params, fv, gcfg)[:, -1, :]

    preds = np.argmax(np.asarray(logits_fn(jnp.asarray(fte))), -1)
    acc = (preds == test["label"]).mean()
    print(f"test accuracy: {acc:.2%} over {len(CLASSES)} classes "
          f"(paper software model: 91.35% on real GSCD)")


if __name__ == "__main__":
    main()
